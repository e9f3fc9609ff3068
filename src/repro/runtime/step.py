"""Shared JAX step machinery for the PS training loops (DESIGN.md §8).

The fused BSP step (vmap worker grads -> ONE masked multi-worker
reduction -> optimizer update) lives here so the legacy lockstep
``PSTrainer`` loop and the event-driven ``ClusterRuntime`` execute the
*same* jitted function — the bsp-equivalence guarantee is by
construction, not by parallel maintenance.

The per-gradient pieces (``build_worker_grad_fn`` / ``build_apply_fn`` /
``build_ef_gate_fn``) are the async/SSP path: under apply-on-arrival
aggregation each worker's gradient is computed against the params
version that worker actually fetched, so the fused vmap (which assumes
one shared params tree) cannot be used. The apply function always takes
a fixed-shape (W, n_packets, payload) buffer — shorter batches are
zero-weight padded — so it compiles exactly once per runtime.

Every ``build_*`` factory memoizes through a module-level jit cache
(DESIGN.md §9) keyed on (api, opt, ltp, plan geometry, W, protocol):
constructing a second ``ClusterRuntime``/``PSTrainer`` over the same
model and config reuses the already-compiled step instead of paying
XLA compilation again — that compile used to dominate the runtime DES
benchmark's wall clock. Cached entries pin their api/opt objects (the
key uses object identity), and the cache is LRU-bounded.
"""
from __future__ import annotations

from collections import OrderedDict
from typing import Callable, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import LTPConfig
from repro.core import ltp_sync as ls
from repro.core import packets as pk
from repro.optim import Optimizer

_JIT_CACHE: "OrderedDict[tuple, Tuple[Callable, tuple]]" = OrderedDict()
_JIT_CACHE_MAX = 32


def _plan_key(plan) -> tuple:
    """Structural identity of a PacketPlan (its arrays are unhashable)."""
    return (plan.packet_floats, plan.n_packets, plan.leaf_shapes,
            plan.leaf_offsets, plan.critical.tobytes())


def _cached(key: tuple, pins: tuple, build: Callable) -> Callable:
    """Return the memoized build() result for ``key``. ``pins`` holds
    strong references to the identity-keyed objects (api/opt) so their
    ids cannot be recycled while the entry lives."""
    hit = _JIT_CACHE.get(key)
    if hit is not None:
        _JIT_CACHE.move_to_end(key)
        return hit[0]
    fn = build()
    _JIT_CACHE[key] = (fn, pins)
    while len(_JIT_CACHE) > _JIT_CACHE_MAX:
        _JIT_CACHE.popitem(last=False)
    return fn


def build_fused_step(api, opt: Optimizer, ltp: LTPConfig, plan, w: int,
                     protocol: str):
    """The lockstep/BSP train step: per-worker grads via vmap, one fused
    masked reduction (kernel-backed under sync_backend="pallas"), one
    optimizer update. Signature:

      step(params, opt_state, residual, batch, masks, frac, lr)
        -> (params, opt_state, residual, mean_loss, realized_frac)
    """
    # id() keys a process-local jit cache only (api/opt objects are
    # unhashable); cache identity never touches the replayed sim state.
    key = ("fused", id(api), id(opt), ltp, _plan_key(plan), w, protocol)  # replint: ok(determinism)
    return _cached(key, (api, opt), lambda: _build_fused_step(
        api, opt, ltp, plan, w, protocol))


def _build_fused_step(api, opt: Optimizer, ltp: LTPConfig, plan, w: int,
                      protocol: str):
    use_ltp = protocol == "ltp"

    def per_worker_grads(params, batch):
        def one(b):
            return jax.value_and_grad(lambda p: api.loss_fn(p, b))(params)
        return jax.vmap(one)(batch)   # (W,) losses, (W, ...) grads

    def step(params, opt_state, residual, batch, masks, frac, lr):
        losses, grads_w = per_worker_grads(params, batch)
        flat_w = jax.vmap(lambda g: pk.flatten(plan, g))(grads_w)
        if use_ltp:
            # the PS hot loop: ONE fused masked multi-worker reduction
            # (kernels.packet_reduce under sync_backend="pallas")
            if residual is not None:
                # error feedback materializes the gated stream anyway —
                # gate once (dropfill under pallas), reduce the result
                flat_w = flat_w + residual
                sent = ls.apply_delivery(
                    flat_w.reshape(w * plan.n_packets, plan.packet_floats),
                    masks.reshape(-1), backend=ltp.sync_backend,
                ).reshape(flat_w.shape)
                new_residual = flat_w - sent
                mean_flat = ls.reduce_packet_stream(
                    sent, masks, ltp, w, expected_frac=frac,
                    premasked=True)
            else:
                new_residual = None
                mean_flat = ls.reduce_packet_stream(
                    flat_w, masks, ltp, w, expected_frac=frac)
            realized = jnp.mean(masks)
        else:
            mean_flat = jnp.mean(flat_w, axis=0)
            new_residual = residual
            realized = jnp.ones(())
        dtypes = [x.dtype for x in jax.tree_util.tree_leaves(params)]
        mean_grads = pk.unflatten(plan, mean_flat, dtypes)
        updates, opt_state = opt.update(mean_grads, opt_state, params, lr)
        params = jax.tree.map(lambda p, u: p + u, params, updates)
        return params, opt_state, new_residual, jnp.mean(losses), realized

    return jax.jit(step)


def build_worker_grad_fn(api, plan):
    """One worker's gradient against ITS OWN params snapshot (the
    async/SSP compute leg): (params, batch_slice) -> (loss, flat packets
    of shape (n_packets, packet_floats))."""
    key = ("grad", id(api), _plan_key(plan))  # replint: ok(determinism)

    def build():
        @jax.jit
        def grad_fn(params, batch):
            loss, grads = jax.value_and_grad(
                lambda p: api.loss_fn(p, batch))(params)
            return loss, pk.flatten(plan, grads)

        return grad_fn

    return _cached(key, (api,), build)


def build_ef_gate_fn(ltp: LTPConfig):
    """Error-feedback gate for the per-gradient path: accumulate what the
    network dropped, re-add it next round (EF-SGD, DESIGN.md §2)."""

    def build():
        @jax.jit
        def gate(flat, residual, mask):
            flat = flat + residual
            sent = ls.apply_delivery(flat, mask, backend=ltp.sync_backend)
            return sent, flat - sent

        return gate

    return _cached(("ef", ltp), (), build)


def build_apply_fn(api, opt: Optimizer, ltp: LTPConfig, plan, w: int,
                   premasked: bool = False):
    """PS-side apply for an admitted batch of gradients (async/SSP).

    (params, opt_state, stacked (W, n, p), masks (W, n), weights (W,),
     frac, lr) -> (params, opt_state).

    The reduction divides by the cluster size ``w`` regardless of how
    many gradients the batch holds (zero-weight rows contribute nothing),
    so each admitted gradient lands with effective step lr * weight / W —
    the same per-contribution scale as one BSP iteration. ``weights``
    carries the policy's staleness damping (``ls.staleness_weights``).
    Note: under "count" compensation the per-packet deliverer count is
    taken within the admitted batch.
    """
    key = ("apply", id(api), id(opt), ltp, _plan_key(plan), w, premasked)  # replint: ok(determinism)

    def build():
        @jax.jit
        def apply(params, opt_state, stacked, masks, weights, frac, lr):
            mean_flat = ls.reduce_packet_stream(
                stacked, masks, ltp, w, expected_frac=frac,
                worker_weights=weights, premasked=premasked)
            dtypes = [x.dtype for x in jax.tree_util.tree_leaves(params)]
            mean_grads = pk.unflatten(plan, mean_flat, dtypes)
            updates, opt_state = opt.update(mean_grads, opt_state, params,
                                            lr)
            params = jax.tree.map(lambda p, u: p + u, params, updates)
            return params, opt_state

        return apply

    return _cached(key, (api, opt), build)


def draw_delivery_masks(plan, w: int, rng: np.random.Generator,
                        frac: np.ndarray,
                        mask_trace: np.ndarray = None,
                        it: int = 0) -> np.ndarray:
    """(W, n_packets) float32 per-(worker, packet) delivery mask.

    From the DES ``mask_trace`` when given (the trace's packet stream is
    tiled/cropped onto the plan's packets), else Bernoulli(frac) per
    packet. Critical packets are always pinned to 1 — the CQ retransmit
    guarantee (paper §III-E).
    """
    n = plan.n_packets
    if mask_trace is not None:
        m = mask_trace[it % len(mask_trace)]
        reps = -(-n // m.shape[1])
        m = np.tile(m, (1, reps))[:, :n].astype(np.float32)
    else:
        m = (rng.random((w, n)) < np.asarray(frac)[:, None]).astype(np.float32)
    m[:, plan.critical] = 1.0
    return m


def tile_mask_onto_plan(plan, mask_row: np.ndarray) -> np.ndarray:
    """(n_transport_pkts,) bool -> (plan.n_packets,) float32, tiled/cropped
    with criticals pinned — one worker's DES delivery state mapped onto
    the packet plan the aggregation kernels consume."""
    n = plan.n_packets
    reps = -(-n // len(mask_row))
    m = np.tile(mask_row, reps)[:n].astype(np.float32)
    m[plan.critical] = 1.0
    return m
