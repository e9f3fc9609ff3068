"""jit'd public wrappers around the Pallas kernels.

Handle padding to the kernels' tile constraints (lane-width payload,
block-multiple packet counts) and strip it on the way out, so callers can
use arbitrary packet geometries. ``interpret=None`` (the default here)
compiles the kernels on a TPU and interprets them on any other backend
(``common.interpret_mode``).

Dispatch cache (DESIGN.md §9): each (interpret, donate) variant of a
wrapper is built exactly once through ``_variant``; within a variant,
``jax.jit`` keys compiled executables by shape, so repeated calls with
the same packet geometry pay zero retrace/recompile. ``donate=True``
donates the packet-stream buffer to the kernel (the output aliases the
input's memory on backends that support aliasing — TPU; a no-op in
interpret mode) — the caller's array is consumed, so only opt in when
the stream is dead after the call (e.g. a PS hot loop that immediately
overwrites it).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import common
from repro.kernels import dropfill as _df
from repro.kernels import packet_reduce as _pr
from repro.kernels import randomk as _rk


def _pad_to(x, m: int, axis: int):
    r = x.shape[axis] % m
    if r == 0:
        return x, 0
    pad = m - r
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), pad


@functools.lru_cache(maxsize=None)
def _variant(fn_name: str, interpret: bool, donate: bool, *static):
    """Shape-keyed jit cache: one jitted callable per (wrapper,
    interpret, donate, static-args) variant; jax.jit's own cache keys
    the compiled executable by input shapes under it."""
    core = {
        "dropfill": _dropfill_core,
        "packet_reduce": _packet_reduce_core,
        "randomk": _randomk_core,
    }[fn_name]
    kw = {"compensation": static[0]} if fn_name == "packet_reduce" else {}
    fn = functools.partial(core, interpret=interpret, **kw)
    return jax.jit(fn, donate_argnums=(0,) if donate else ())


def _dropfill_core(packets, mask, scale, *, interpret: bool):
    x, pad_p = _pad_to(packets.astype(jnp.float32), 128, 1)
    x, pad_n = _pad_to(x, _df.BLOCK_P, 0)
    m, _ = _pad_to(mask.astype(jnp.float32), _df.BLOCK_P, 0)
    s, _ = _pad_to(scale.astype(jnp.float32), _df.BLOCK_P, 0)
    out = _df.dropfill(x, m, s, interpret=interpret)
    out = out[: packets.shape[0], : packets.shape[1]]
    return out.astype(packets.dtype)


def ltp_dropfill(packets, mask, scale=None, *,
                 interpret: Optional[bool] = None, donate: bool = False):
    """packets: (n_packets, payload) any-float; mask: (n_packets,) {0,1};
    scale: optional (n_packets,) compensation. Zero-fills lost packets."""
    if scale is None:
        scale = jnp.ones_like(mask)
    return _variant("dropfill", common.interpret_mode(interpret),
                    bool(donate))(packets, mask, scale)


def _packet_reduce_core(packets, mask, *, compensation: str,
                        interpret: bool):
    x, _ = _pad_to(packets.astype(jnp.float32), 128, 2)
    x, _ = _pad_to(x, _pr.BLOCK_P, 1)
    m, _ = _pad_to(mask.astype(jnp.float32), _pr.BLOCK_P, 1)
    out = _pr.packet_reduce(x, m, compensation=compensation,
                            interpret=interpret)
    return out[: packets.shape[1], : packets.shape[2]]


def ltp_packet_reduce(packets, mask, *, compensation: str = "paper",
                      interpret: Optional[bool] = None,
                      donate: bool = False):
    """packets: (W, n_packets, payload); mask: (W, n_packets)."""
    return _variant("packet_reduce", common.interpret_mode(interpret),
                    bool(donate), compensation)(packets, mask)


def _randomk_core(x, u, k_frac, *, interpret: bool):
    orig_shape = x.shape
    flat = x.reshape(-1)
    uf = u.reshape(-1)
    n = flat.shape[0]
    cols = _rk.BLOCK_C
    rows = -(-n // cols)
    pad = rows * cols - n
    flat = jnp.pad(flat, (0, pad)).reshape(rows, cols)
    uf = jnp.pad(uf, (0, pad), constant_values=2.0).reshape(rows, cols)
    flat, _ = _pad_to(flat, _rk.BLOCK_R, 0)
    uf, _ = _pad_to(uf, _rk.BLOCK_R, 0)
    # padded uniforms = 2.0 > k  ->  padding never kept
    out = _rk.randomk(flat, uf, k_frac, interpret=interpret)
    return out.reshape(-1)[:n].reshape(orig_shape)


def randomk_sparsify(x, u, k_frac, *, interpret: Optional[bool] = None):
    """Elementwise Random-k keep mask via uniforms ``u`` (same shape)."""
    return _variant("randomk", common.interpret_mode(interpret), False)(
        x, u, k_frac)
