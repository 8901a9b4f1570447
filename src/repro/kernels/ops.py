"""jit'd dispatch wrappers for the Pallas kernels.

The one place that decides interpret mode: on the CPU backend (tests)
the kernels execute under interpret=True, which runs the kernel body
block-by-block in the Pallas interpreter — bit-level semantics of the
BlockSpec tiling without TPU hardware.  On every other backend the
compiled kernel runs; pass `interpret` explicitly to override.
"""
from __future__ import annotations

import functools

import jax

from . import day_scan as _day
from . import flash_attention as _fa
from . import ssd_scan as _ssd


def default_interpret() -> bool:
    """Interpret mode only where the backend is the CPU."""
    return jax.default_backend() == "cpu"


def _interpret(interpret: bool | None) -> bool:
    return default_interpret() if interpret is None else interpret


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q",
                                             "block_k", "interpret"))
def flash_attention(q, k, v, *, causal=True, window=None, block_q=512,
                    block_k=512, interpret=None):
    return _fa.flash_attention(q, k, v, causal=causal, window=window,
                               block_q=block_q, block_k=block_k,
                               interpret=_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x, dt, A, B, C, *, chunk=128, interpret=None):
    return _ssd.ssd_scan(x, dt, A, B, C, chunk=chunk,
                         interpret=_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def day_scan(tables, *, chunk=128, interpret=None):
    return _day.day_scan(tables, chunk=chunk,
                         interpret=_interpret(interpret))
