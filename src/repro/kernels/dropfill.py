"""Pallas TPU kernel: bubble-fill + compensation over packet tiles.

The PS-side hot loop of LTP-sync applies, per packet, `out = g * mask * scale`
over the flattened gradient stream laid out as (n_packets, payload). The
payload is lane-aligned (the paper's *padding bubble* generalized from
4-byte float alignment to the TPU's 128-float lane width — DESIGN.md §2),
so a whole packet maps to whole vector lanes and a lost packet zeroes
aligned spans. Memory-bound: tiles stream HBM -> VMEM once.

Block shape: (BLOCK_P, payload) with payload padded to a 128 multiple by
``ops.ltp_dropfill``; BLOCK_P=256 keeps the working set ~256*384*4B = 384KB
in VMEM (well under the ~16MB/core budget, leaving room for double
buffering).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
from jax.experimental import pallas as pl

from repro.kernels import common

BLOCK_P = 256


def _dropfill_kernel(pkt_ref, gate_ref, out_ref):
    """pkt: (BLOCK_P, payload); gate: (BLOCK_P, 1) = mask*scale."""
    out_ref[...] = pkt_ref[...] * gate_ref[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def dropfill(packets, mask, scale, *, interpret: Optional[bool] = None):
    """packets: (n_packets, payload) f32; mask/scale: (n_packets,) f32.

    Requires payload % 128 == 0 and n_packets % BLOCK_P == 0 (the ops.py
    wrapper pads); returns packets * mask * scale.
    """
    n, p = packets.shape
    assert p % 128 == 0, f"payload {p} not lane-aligned"
    assert n % BLOCK_P == 0, f"n_packets {n} not a multiple of {BLOCK_P}"
    gate = (mask * scale)[:, None].astype(packets.dtype)
    grid = (n // BLOCK_P,)
    vma = common.out_vma(packets, gate)
    return pl.pallas_call(
        _dropfill_kernel,
        out_shape=jax.ShapeDtypeStruct((n, p), packets.dtype, vma=vma),
        grid=grid,
        in_specs=[
            pl.BlockSpec((BLOCK_P, p), lambda i: (i, 0)),
            pl.BlockSpec((BLOCK_P, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((BLOCK_P, p), lambda i: (i, 0)),
        interpret=common.pallas_interpret(interpret, vma),
    )(packets, gate)
