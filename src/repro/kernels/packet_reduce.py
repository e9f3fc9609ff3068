"""Pallas TPU kernel: PS-side masked multi-worker packet reduction.

Aggregates W workers' packetized gradients with per-(worker, packet)
delivery masks and bubble-fill compensation:

    paper:  out[p] = sum_w g[w,p] * m[w,p] / W
    count:  out[p] = sum_w g[w,p] * m[w,p] / max(sum_w m[w,p], 1)

The worker axis is the grid's trailing, sequential dimension: each step
reads a block of ``worker_block(W)`` workers' tiles and adds them into
the (BLOCK_P, payload) f32 output tile, which stays in VMEM across the
worker axis and is written back once. Each input tile is read once and
each output tile written once: one HBM pass, the roofline optimum for
this memory-bound reduction, with VMEM use that does not grow with W.
This is the TPU adaptation of the paper's PS aggregation hot loop (their
C++ server thread).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import common

BLOCK_P = 128
#: most workers read per grid step
MAX_WORKER_BLOCK = 8


def worker_block(w: int) -> int:
    """Workers per grid step: the largest divisor of ``w`` that is at
    most ``MAX_WORKER_BLOCK`` (so no padding worker rows are read)."""
    return max(d for d in range(1, min(w, MAX_WORKER_BLOCK) + 1)
               if w % d == 0)


def _reduce_kernel(pkts_ref, mask_ref, out_ref, *cnt_ref, n_workers: int,
                   compensation: str):
    """pkts: (WB, BLOCK_P, payload); mask: (WB, BLOCK_P, 1); out: the
    (BLOCK_P, payload) f32 accumulator; cnt_ref: the (BLOCK_P, 1) f32
    deliverer count, present under "count" compensation only."""
    j = pl.program_id(1)
    last = j == pl.num_programs(1) - 1

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.zeros(out_ref.shape, jnp.float32)
        for c in cnt_ref:
            c[...] = jnp.zeros(c.shape, jnp.float32)

    acc = out_ref[...]
    cnt = cnt_ref[0][...] if cnt_ref else None
    for w in range(pkts_ref.shape[0]):          # static unroll
        m = mask_ref[w]
        acc = acc + pkts_ref[w].astype(jnp.float32) * m
        if cnt_ref:
            cnt = cnt + m

    @pl.when(jnp.logical_not(last))
    def _carry():
        out_ref[...] = acc
        for c in cnt_ref:
            c[...] = cnt

    @pl.when(last)
    def _finish():
        if compensation == "count":
            out_ref[...] = acc / jnp.maximum(cnt, 1.0)
        else:
            out_ref[...] = acc / n_workers


@functools.partial(jax.jit, static_argnames=("compensation", "interpret"))
def packet_reduce(packets, mask, *, compensation: str = "paper",
                  interpret: Optional[bool] = None):
    """packets: (W, n_packets, payload) f32; mask: (W, n_packets) f32.

    Requires payload % 128 == 0, n_packets % BLOCK_P == 0. Returns
    (n_packets, payload) float32.
    """
    w, n, p = packets.shape
    assert p % 128 == 0 and n % BLOCK_P == 0, (w, n, p)
    mask3 = mask[..., None].astype(jnp.float32)
    wb = worker_block(w)
    kernel = functools.partial(
        _reduce_kernel, n_workers=w, compensation=compensation
    )
    scratch = ([pltpu.VMEM((BLOCK_P, 1), jnp.float32)]
               if compensation == "count" else [])
    vma = common.out_vma(packets, mask3)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((n, p), jnp.float32, vma=vma),
        grid=(n // BLOCK_P, w // wb),
        in_specs=[
            pl.BlockSpec((wb, BLOCK_P, p), lambda i, j: (j, i, 0)),
            pl.BlockSpec((wb, BLOCK_P, 1), lambda i, j: (j, i, 0)),
        ],
        out_specs=pl.BlockSpec((BLOCK_P, p), lambda i, j: (i, 0)),
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=common.pallas_interpret(interpret, vma),
    )(packets, mask3)


def tree_reduce(packets, mask, rack_of, *, compensation: str = "paper",
                interpret: Optional[bool] = None):
    """Hierarchical (rack → root) masked reduction, DESIGN.md §11.

    Models the aggregation tree's math: each rack's ToR partially reduces
    its members' delivered packets with the same kernel the PS uses, the
    root combines the per-rack partial sums. ``rack_of`` maps worker w →
    rack id. Returns (n_packets, payload) float32 equal to the flat
    ``packet_reduce(packets, mask)`` to float tolerance (pinned by
    tests/test_aggtree.py) — the tree moves bytes, never the answer.

    Per rack the kernel's own normalizations are inverted back to raw
    masked sums (x rack W for "paper", x per-packet counts for "count"),
    so the root division is the only lossy float step beyond summation
    order.
    """
    w, n, p = packets.shape
    racks = {}
    for f in range(w):
        racks.setdefault(int(rack_of(f)), []).append(f)
    acc = jnp.zeros((n, p), jnp.float32)
    cnt = jnp.zeros((n, 1), jnp.float32)
    for members in racks.values():
        sub_p = packets[jnp.array(members)]
        sub_m = mask[jnp.array(members)]
        partial = packet_reduce(sub_p, sub_m, compensation=compensation,
                                interpret=interpret)
        if compensation == "count":
            c = jnp.sum(sub_m.astype(jnp.float32), axis=0)[:, None]
            acc = acc + partial * jnp.maximum(c, 1.0)
            cnt = cnt + c
        else:
            acc = acc + partial * len(members)
            cnt = cnt + jnp.sum(sub_m.astype(jnp.float32), axis=0)[:, None]
    if compensation == "count":
        return acc / jnp.maximum(cnt, 1.0)
    return acc / w
