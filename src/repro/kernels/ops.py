"""Public jit'd entry points for the Pallas kernels.

Dispatch policy: on TPU the Pallas kernel compiles natively (Mosaic);
on any other backend the kernel body executes in ``interpret=True``
mode, which is how the test suite runs them on CPU.  The kernels take
``interpret`` as a required keyword, so a caller that bypasses this
module has to choose.  ``ref.py`` holds the pure-XLA references the
tests compare against.
"""
from __future__ import annotations

from functools import partial

import jax

from . import ref  # noqa: F401  (re-exported reference path)
from .flash_attention import flash_attention_pallas
from .hash32x2 import hash32x2_pallas
from .segment_reduce import segment_sum_sorted_pallas
from .substr_find import exists_before_pallas, substr_find_pallas
from .wkv6 import wkv6_pallas


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _interpret() -> bool:
    return not on_tpu()


@jax.jit
def hash32x2(cols: jax.Array) -> jax.Array:
    return hash32x2_pallas(cols, interpret=_interpret())


@partial(jax.jit, static_argnames=("num_segments",))
def segment_sum_sorted(values, seg_ids, num_segments: int):
    return segment_sum_sorted_pallas(
        values, seg_ids, num_segments, interpret=_interpret()
    )


def substr_find(packed, lens, pattern, start=None):
    return substr_find_pallas(packed, lens, pattern, start, interpret=_interpret())


def exists_before(packed, lens, pat_a, pat_b):
    return exists_before_pallas(packed, lens, pat_a, pat_b, interpret=_interpret())


@partial(jax.jit, static_argnames=("causal", "bq", "bk"))
def flash_attention(q, k, v, causal: bool = True, bq: int = 128, bk: int = 128):
    return flash_attention_pallas(
        q, k, v, causal=causal, bq=bq, bk=bk, interpret=_interpret()
    )


@partial(jax.jit, static_argnames=("bt",))
def wkv6(r, k, v, w, u, state=None, bt: int = 64):
    return wkv6_pallas(r, k, v, w, u, state, bt=bt, interpret=_interpret())
