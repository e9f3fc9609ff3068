"""Pallas TPU kernel: Random-k gradient sparsification (paper §II-C).

Keeps each element where a precomputed uniform draw falls under ``k_frac``
(threshold-controlled Random-k — the sparsifier whose semantics LTP's
packet loss emulates, paper Fig 5). Uniforms are generated outside the
kernel (jax.random) and streamed in; the kernel is a pure select, one HBM
pass — the point of the kernel is fusing select+scale so the sparsified
tensor is never materialized twice.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import common

BLOCK_R = 256
BLOCK_C = 512


def _randomk_kernel(x_ref, u_ref, k_ref, out_ref):
    k = k_ref[0, 0]
    out_ref[...] = jnp.where(u_ref[...] < k, x_ref[...],
                             jnp.zeros_like(x_ref[...]))


@functools.partial(jax.jit, static_argnames=("interpret",))
def randomk(x, u, k_frac, *, interpret: Optional[bool] = None):
    """x, u: (rows, cols) with rows % BLOCK_R == 0, cols % BLOCK_C == 0;
    k_frac: scalar in [0,1]. Returns x sparsified."""
    r, c = x.shape
    assert r % BLOCK_R == 0 and c % BLOCK_C == 0, (r, c)
    k = jnp.full((1, 1), k_frac, jnp.float32)
    grid = (r // BLOCK_R, c // BLOCK_C)
    vma = common.out_vma(x, u, k)
    return pl.pallas_call(
        _randomk_kernel,
        out_shape=jax.ShapeDtypeStruct((r, c), x.dtype, vma=vma),
        grid=grid,
        in_specs=[
            pl.BlockSpec((BLOCK_R, BLOCK_C), lambda i, j: (i, j)),
            pl.BlockSpec((BLOCK_R, BLOCK_C), lambda i, j: (i, j)),
            pl.BlockSpec((1, 1), lambda i, j: (0, 0)),
        ],
        out_specs=pl.BlockSpec((BLOCK_R, BLOCK_C), lambda i, j: (i, j)),
        interpret=common.pallas_interpret(interpret, vma),
    )(x, u, k)
