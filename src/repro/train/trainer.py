"""Train-step builders.

``make_plain_train_step``  — GSPMD/fsdp baseline (lossless sync, the TCP/
                             BBR-transport analogue at the numerics level).
``make_ltp_train_step``    — LTP as a first-class feature at scale: the
                             whole fwd/bwd runs inside a shard_map that is
                             MANUAL over the worker axes (pod and/or data)
                             and AUTO over the rest, so per-worker gradient
                             contributions exist explicitly and are
                             packet-masked before the psum (paper §III).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.config import LTPConfig
from repro.core import ltp_sync as ls
from repro.models.api import ModelApi
from repro.models.sharding import ShardCtx
from repro.optim import Optimizer


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class TrainState:
    params: Any
    opt_state: Any
    step: jnp.ndarray


def init_state(api: ModelApi, opt: Optimizer, key) -> TrainState:
    params = api.init(key)
    return TrainState(params=params, opt_state=opt.init(params),
                      step=jnp.zeros((), jnp.int32))


def make_plain_train_step(api: ModelApi, opt: Optimizer,
                          mesh=None) -> Callable:
    """Global-loss pjit step; gradient sync is GSPMD's exact all-reduce."""
    ctx = ShardCtx(mesh)

    def step(state: TrainState, batch, lr):
        loss, grads = jax.value_and_grad(
            lambda p: api.loss_fn(p, batch, ctx=ctx)
        )(state.params)
        updates, opt_state = opt.update(grads, state.opt_state, state.params, lr)
        params = jax.tree.map(lambda p, u: p + u, state.params, updates)
        return (
            TrainState(params, opt_state, state.step + 1),
            {"loss": loss},
        )

    return step


def make_ltp_train_step(api: ModelApi, opt: Optimizer, mesh,
                        ltp: LTPConfig, worker_axes: Tuple[str, ...],
                        batch_specs) -> Callable:
    """LTP-synced step (sharded, v2 leafwise-packet masking).

    worker_axes: the mesh axes along which the model is REPLICATED and
    whose members act as the paper's workers — ('pod',) for cross-DC LTP
    (the flagship multi-pod config: ICI inside a pod is lossless, the
    pod-to-pod DCN link is where loss tolerance pays), or ('data',) /
    ('pod','data') for classic PS emulation.

    batch_specs: pytree of PartitionSpecs for the batch (full specs are
    fine — they are restricted to the manual worker axes here; the auto
    axes are constrained inside via ShardCtx).
    """
    n_workers = 1
    for a in worker_axes:
        n_workers *= mesh.shape[a]
    # Pallas TPU kernels lower only where every mesh axis is manual. An
    # axis of size 1 has nothing to partition, so it is made manual too.
    manual = set(worker_axes) | {a for a in mesh.axis_names
                                 if mesh.shape[a] == 1}
    ctx = ShardCtx(mesh, exclude=tuple(manual))

    def restrict(spec: P) -> P:
        out = []
        for entry in spec:
            if entry is None:
                out.append(None)
                continue
            names = (entry,) if isinstance(entry, str) else tuple(entry)
            keep = tuple(n for n in names if n in worker_axes)
            out.append(keep[0] if len(keep) == 1 else (keep or None))
        return P(*out)

    batch_specs = jax.tree.map(restrict, batch_specs,
                               is_leaf=lambda x: isinstance(x, P))

    def inner(params, opt_state, mstep, batch, frac, key, lr):
        loss, grads = jax.value_and_grad(
            lambda p: api.loss_fn(p, batch, ctx=ctx)
        )(params)
        synced, realized = ls.masked_psum_leafwise(
            grads, key, frac, ltp, worker_axes, n_workers
        )
        updates, opt_state = opt.update(synced, opt_state, params, lr)
        params = jax.tree.map(lambda p, u: p + u, params, updates)
        loss_g = jax.lax.pmean(loss, worker_axes)
        return params, opt_state, mstep + 1, loss_g, realized

    def inner_zero(params, m_pkts, mstep, batch, frac, key, lr):
        loss, grads = jax.value_and_grad(
            lambda p: api.loss_fn(p, batch, ctx=ctx)
        )(params)
        deltas, m_pkts, realized = ls.masked_rs_update_leafwise(
            grads, params, m_pkts, key, frac, ltp, worker_axes, n_workers, lr
        )
        loss_g = jax.lax.pmean(loss, worker_axes)
        return deltas, m_pkts, mstep + 1, loss_g, realized

    worker_spec = (worker_axes if len(worker_axes) > 1 else worker_axes[0])

    def _zero_step(state: TrainState, batch, frac, key, lr):
        n_leaves = len(state.opt_state["m_pkts"])
        m_specs = [P(worker_spec, None)] * n_leaves
        deltas, m_pkts, mstep, loss, realized = jax.shard_map(
            inner_zero,
            mesh=mesh,
            in_specs=(rep, m_specs, rep, batch_specs, rep, rep, rep),
            out_specs=(m_specs, m_specs, rep, rep, rep),
            axis_names=manual,
            check_vma=True,
        )(state.params, state.opt_state["m_pkts"], state.step, batch, frac,
          key, lr)
        # apply the worker-sharded packet deltas in auto land (GSPMD
        # all-gathers the bf16 buffers — the cheap leg of RS+AG)
        p_leaves, treedef = jax.tree_util.tree_flatten(state.params)
        new_leaves = [
            p + ls._from_packets(d.astype(jnp.float32), p.shape, p.dtype)
            for p, d in zip(p_leaves, deltas)
        ]
        params = jax.tree_util.tree_unflatten(treedef, new_leaves)
        return (
            TrainState(params, {"m_pkts": m_pkts}, mstep),
            {"loss": loss, "delivered_frac": realized},
        )

    rep = P()  # replicated w.r.t. the manual worker axes

    def step(state: TrainState, batch, frac, key, lr):
        if isinstance(state.opt_state, dict) and "m_pkts" in state.opt_state:
            return _zero_step(state, batch, frac, key, lr)
        params, opt_state, mstep, loss, realized = jax.shard_map(
            inner,
            mesh=mesh,
            in_specs=(rep, rep, rep, batch_specs, rep, rep, rep),
            out_specs=(rep, rep, rep, rep, rep),
            axis_names=manual,
            check_vma=True,
        )(state.params, state.opt_state, state.step, batch, frac, key, lr)
        return (
            TrainState(params, opt_state, mstep),
            {"loss": loss, "delivered_frac": realized},
        )

    return step
