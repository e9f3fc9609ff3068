"""Loss-tolerant gradient synchronization — the paper's technique as a
first-class JAX feature.

Semantics (paper §III): during *gathering*, each worker's gradient
contribution is packetized; non-critical packets are delivered i.i.d. with
the Early-Close-controlled fraction; lost packets are bubble-filled with
zeros at the PS. *Broadcasting* (the reduced result) is reliable — here it
is simply the psum output, exactly the paper's asymmetry.

Mapping onto the mesh: worker = (pod, data) index; the model axis shards
the payload itself (each model shard is its own PS, as in multi-PS
deployments), so packetization is per-device-local and the sync is pure
elementwise work + one psum over the data axes — implemented as a fully
manual ``jax.shard_map`` (no tensor resharding, no extra collectives).

Compensation modes (beyond-paper, DESIGN.md §2):
  paper     sum/W             (plain mean with zero bubbles — the paper)
  count     sum/count         (per-packet unbiased mean over deliverers)
  expected  sum/(W*E[frac])   (global rescale)

Error feedback (beyond-paper): each worker accumulates the packets it
failed to deliver and re-adds them next iteration (EF-SGD style).

Aggregation backends (DESIGN.md §7): every masked-aggregation step
dispatches through ``apply_delivery`` / ``reduce_packet_stream`` on
``LTPConfig.sync_backend`` — ``python`` is the pure-jnp reference,
``pallas`` runs the fused ``kernels.dropfill`` / ``kernels.packet_reduce``
tiles (one HBM pass for the whole PS hot loop; compiled on a TPU,
interpreted elsewhere).
Both backends agree to float tolerance (tests/test_sync_backend.py).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.config import LTPConfig
from repro.core import packets as pk
from repro.kernels import common as kcommon
from repro.kernels import ops as kops
from repro.models.sharding import dp_axes

# number of leading mesh axes used as the worker index, in order
_DP_ORDER = ("pod", "data")


# ----------------------------------------------------------------------------
# backend dispatch: the PS hot loop as fused kernels or jnp reference
# ----------------------------------------------------------------------------

#: python/pallas crossover in stream elements (W * n_packets * payload)
#: for COMPILED kernels (a TPU backend): below it the jnp
#: reference wins on dispatch overhead, above it the fused single-pass
#: tiles win on memory traffic. In interpret mode the kernel body runs
#: in the Python interpreter and never beats jnp, so "auto" always
#: resolves to python there — measured by ``benchmarks.kernel_bench``
#: (``sync_crossover_elems`` in BENCH_kernels.json).
AUTO_CROSSOVER_ELEMS = 1 << 22


def resolve_backend(backend: str, n_elems: int,
                    interpret: bool = True) -> str:
    """Resolve ``sync_backend="auto"`` to a concrete backend for a
    stream of ``n_elems`` elements; passes explicit backends through.
    The guarantee the benchmarks gate: auto is never a regression — it
    picks python below the measured crossover and pallas above it, and
    interpret-mode kernels (CPU) never win, so auto==python there."""
    if backend != "auto":
        return backend
    if interpret or n_elems < AUTO_CROSSOVER_ELEMS:
        return "python"
    return "pallas"


def apply_delivery(packets, mask, scale=None, *, backend: str = "python"):
    """Bubble-fill + compensation gate: ``packets * mask * scale``.

    packets: (n_packets, payload); mask/scale: (n_packets,). The pallas
    backend runs ``kernels.dropfill`` through the ``ops`` padding wrappers
    (arbitrary geometry in, lane-aligned tiles inside); ``"auto"``
    resolves via ``resolve_backend`` on the stream size.
    """
    backend = resolve_backend(backend, packets.size,
                              kcommon.interpret_mode())
    if backend == "pallas":
        m = mask if scale is None else mask * scale
        return kops.ltp_dropfill(packets, m)
    gate = mask if scale is None else mask * scale
    return packets * gate[:, None].astype(packets.dtype)


def staleness_weights(staleness, damping: float) -> np.ndarray:
    """(W,) contribution weights for gradients ``staleness`` iterations
    old: 1 / (1 + damping * s) — the staleness-aware damping the
    async/SSP aggregation policies feed to ``reduce_packet_stream`` as
    ``worker_weights`` (DESIGN.md §8). The coefficient comes from
    ``LTPConfig.staleness_comp`` (or a policy override); 0 gives the
    identity (every admitted gradient weighs 1). This is THE damping
    law — policies call it rather than re-deriving it."""
    s = np.asarray(staleness, np.float32)
    return 1.0 / (1.0 + float(damping) * np.maximum(s, 0.0))


def reduce_packet_stream(packets_w, masks_w, ltp: LTPConfig, n_workers: int,
                         *, expected_frac=None, backend: Optional[str] = None,
                         premasked: bool = False, worker_weights=None):
    """The PS-side hot loop: one fused masked multi-worker reduction.

    packets_w: (W, n_packets, payload); masks_w: (W, n_packets) {0,1}.
    Returns the (n_packets, payload) compensated mean under
    ``ltp.compensation`` (paper | count | expected; ``expected`` needs
    ``expected_frac``, the Early-Close target fraction).

    backend="pallas" executes ``kernels.packet_reduce`` — the workers are
    summed inside the kernel so each output tile is written once and
    each input tile read once (single HBM pass). backend="python" is the
    jnp reference the kernels are verified against.

    ``premasked=True`` declares that ``packets_w`` has already been gated
    by ``masks_w`` (the error-feedback path materializes the masked
    stream anyway): the python backend skips the multiply; the pallas
    kernel re-applies the {0,1} mask, which is idempotent.

    ``worker_weights`` ((W,) float, optional) damps each worker's
    contribution — staleness-aware compensation under async/SSP
    aggregation (DESIGN.md §8). A weight multiplies the worker's gradient
    exactly as per-contribution learning-rate damping would, so it
    composes identically with every compensation mode and both backends
    (the stream is pre-scaled before the fused reduction).
    """
    backend = resolve_backend(backend or ltp.sync_backend, packets_w.size,
                              kcommon.interpret_mode())
    comp = ltp.compensation
    if worker_weights is not None:
        w_ = jnp.asarray(worker_weights, jnp.float32)
        packets_w = packets_w * w_[:, None, None]
    if backend == "pallas":
        out = kops.ltp_packet_reduce(
            packets_w, masks_w,
            compensation="count" if comp == "count" else "paper")
        if comp == "expected":
            # paper-mode output is sum/W; expected = sum/(W*E[frac])
            ef = (jnp.mean(masks_w) if expected_frac is None
                  else jnp.mean(jnp.asarray(expected_frac)))
            out = out / jnp.maximum(ef, 1e-6)
        return out
    masks_w = masks_w.astype(jnp.float32)
    gated = (packets_w.astype(jnp.float32) if premasked
             else packets_w.astype(jnp.float32) * masks_w[:, :, None])
    tot = jnp.sum(gated, axis=0)
    if comp == "count":
        cnt = jnp.maximum(jnp.sum(masks_w, axis=0), 1.0)
        return tot / cnt[:, None]
    if comp == "expected":
        ef = (jnp.mean(masks_w) if expected_frac is None
              else jnp.mean(jnp.asarray(expected_frac)))
        return tot / (n_workers * jnp.maximum(ef, 1e-6))
    return tot / n_workers


@dataclasses.dataclass(frozen=True)
class LTPSync:
    """Callable gradient synchronizer bound to (mesh, plan, config)."""

    mesh: Any
    plan: pk.PacketPlan
    ltp: LTPConfig
    grad_specs: Any          # pytree of PartitionSpecs matching grads
    n_workers: int

    def residual_spec(self):
        """Global residual: (W, nm, n_packets, packet_floats)."""
        dp = dp_axes(self.mesh)
        nm = self.mesh.shape.get("model", 1) if hasattr(self.mesh.shape, "get") else (
            self.mesh.shape["model"] if "model" in self.mesh.axis_names else 1
        )
        shape = (self.n_workers, nm, self.plan.n_packets, self.plan.packet_floats)
        spec = P(dp if len(dp) > 1 else (dp[0] if dp else None),
                 "model" if "model" in self.mesh.axis_names else None, None, None)
        return jax.ShapeDtypeStruct(shape, jnp.float32), spec

    def init_residual(self):
        sds, spec = self.residual_spec()
        if self.ltp.error_feedback:
            return jnp.zeros(sds.shape, sds.dtype)
        return None

    def __call__(self, grads, frac, key, residual=None):
        """grads: pytree (sharded per grad_specs); frac: (W,) float32
        delivered fraction per worker; key: uint32 PRNG key.

        Returns (synced_grads, new_residual, stats) where stats carries the
        realized delivered fraction (scalar) for logging.
        """
        mesh = self.mesh
        dp = dp_axes(mesh)
        has_model = "model" in mesh.axis_names
        W = self.n_workers
        plan = self.plan
        ltp = self.ltp
        leaf_dtypes = [x.dtype for x in jax.tree_util.tree_leaves(grads)]

        def local(g, frac, key, res):
            # worker index over dp axes (row-major over (pod, data))
            widx = jnp.zeros((), jnp.int32)
            for a in dp:
                widx = widx * mesh.shape[a] + jax.lax.axis_index(a)
            k = jax.random.fold_in(key, widx)
            if has_model:
                k = jax.random.fold_in(k, jax.lax.axis_index("model"))
            flat = pk.flatten(plan, g)
            if res is not None:
                flat = flat + res.reshape(flat.shape)
            mask = pk.delivery_mask(plan, k, frac[widx])
            # bubble-fill gate + compensation both dispatch on the backend:
            # fused dropfill tiles under "pallas", jnp reference otherwise
            sent = apply_delivery(flat, mask, backend=ltp.sync_backend)
            tot = jax.lax.psum(sent, dp)
            if ltp.compensation == "count":
                cnt = jax.lax.psum(mask, dp)
                out = apply_delivery(tot, jnp.ones_like(cnt),
                                     1.0 / jnp.maximum(cnt, 1.0),
                                     backend=ltp.sync_backend)
            elif ltp.compensation == "expected":
                mean_frac = jnp.mean(
                    jnp.where(jnp.asarray(plan.critical), 1.0, jnp.mean(frac))
                )
                out = tot / (W * mean_frac)
            else:  # paper
                out = tot / W
            new_res = (flat - sent).reshape(res.shape) if res is not None else None
            realized = jax.lax.psum(jnp.mean(mask), dp) / W
            return pk.unflatten(plan, out, leaf_dtypes), new_res, realized

        res_in = residual
        sds, res_spec = self.residual_spec()
        args_specs = (self.grad_specs, P(), P())
        out_res_spec = res_spec
        if res_in is None:
            def f(g, fr, k):
                return local(g, fr, k, None)[::2]   # (grads, realized)
            synced, realized = jax.shard_map(
                f,
                mesh=mesh,
                in_specs=args_specs,
                out_specs=(self.grad_specs, P()),
                check_vma=False,
            )(grads, frac, key)
            return synced, None, {"delivered_frac": realized}
        synced, new_res, realized = jax.shard_map(
            local,
            mesh=mesh,
            in_specs=args_specs + (res_spec,),
            out_specs=(self.grad_specs, out_res_spec, P()),
            check_vma=False,
        )(grads, frac, key, res_in)
        return synced, new_res, {"delivered_frac": realized}


def _leaf_packet_mask(i, leaf_shape, key, frac, ltp: LTPConfig):
    """(n_pkts,) float32 delivery mask for leaf index ``i``."""
    size = int(np.prod(leaf_shape)) if leaf_shape else 1
    n_pkts = max(1, -(-size // ltp.packet_floats))
    k = jax.random.fold_in(key, i)
    u = jax.random.uniform(k, (n_pkts,))
    crit = np.zeros(n_pkts, bool)
    c = ltp.critical_per_tensor
    crit[:c] = True
    crit[-c:] = True
    return jnp.where(jnp.asarray(crit), 1.0, (u < frac).astype(jnp.float32))


def _as_packets(leaf, p: int):
    """Row-major (n_pkts, p) float32 view of a leaf (zero-padded tail)."""
    size = int(np.prod(leaf.shape)) if leaf.shape else 1
    n_pkts = max(1, -(-size // p))
    flat = leaf.astype(jnp.float32).reshape(-1)
    pad = n_pkts * p - size
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), jnp.float32)])
    return flat.reshape(n_pkts, p)


def _from_packets(pkts, shape, dtype):
    size = int(np.prod(shape)) if shape else 1
    return pkts.reshape(-1)[:size].reshape(shape).astype(dtype)


def leafwise_packet_masks(grads, key, frac, ltp: LTPConfig):
    """Per-leaf packet delivery masks, broadcast to element space.

    Packets are spans of ``ltp.packet_floats`` contiguous elements in each
    leaf's row-major layout (per-leaf streams; the padding-bubble alignment
    holds within every leaf). The mask expands by broadcast against the
    (n_pkts, p) view — no jnp.repeat (whose flat indexing overflows int32
    on >2^31-element stacked leaves).

    Returns (masks pytree matching grads, packet_masks list).
    """
    leaves, treedef = jax.tree_util.tree_flatten(grads)
    masks, pkt_masks = [], []
    p = ltp.packet_floats
    for i, leaf in enumerate(leaves):
        m = _leaf_packet_mask(i, leaf.shape, key, frac, ltp)
        pkt_masks.append(m)
        view = _as_packets(jnp.ones_like(leaf, jnp.float32), p) * m[:, None]
        masks.append(_from_packets(view, leaf.shape, jnp.float32))
    return jax.tree_util.tree_unflatten(treedef, masks), pkt_masks


def masked_psum_leafwise(grads, key, frac, ltp: LTPConfig, worker_axes,
                         n_workers: int):
    """The in-shard_map body of sharded LTP sync (v2, per-leaf packets).

    Must run inside a shard_map that is MANUAL over ``worker_axes`` (the
    replicated-model data axes — e.g. ('pod',) for cross-DC LTP) and auto
    over everything else. ``frac``: (n_workers,) delivered fraction.
    """
    widx = jnp.zeros((), jnp.int32)
    for a in worker_axes:
        widx = widx * jax.lax.axis_size(a) + jax.lax.axis_index(a)
    k = jax.random.fold_in(key, widx)
    p = ltp.packet_floats
    leaves, treedef = jax.tree_util.tree_flatten(grads)
    out = []
    realized = None
    for i, leaf in enumerate(leaves):
        m = _leaf_packet_mask(i, leaf.shape, k, frac[widx], ltp)
        view = apply_delivery(_as_packets(leaf, p), m,
                              backend=ltp.sync_backend)
        # per-leaf f32 psum: one all-reduce per tensor with a uniform dtype
        # (XLA:CPU CHECK-fails on one huge mixed-dtype tuple all-reduce —
        # and per-tensor reduces are what a production runtime overlaps
        # with backward anyway)
        tot = jax.lax.psum(view, worker_axes)
        if ltp.compensation == "count":
            cnt = jax.lax.psum(m, worker_axes)
            tot = tot / jnp.maximum(cnt, 1.0)[:, None]
        elif ltp.compensation == "expected":
            tot = tot / (n_workers * jnp.maximum(jnp.mean(frac), 1e-6))
        else:  # paper
            tot = tot / n_workers
        out.append(_from_packets(tot, leaf.shape, leaf.dtype))
        if realized is None:
            realized = jax.lax.psum(jnp.mean(m), worker_axes) / n_workers
    synced = jax.tree_util.tree_unflatten(treedef, out)
    return synced, realized


def masked_rs_update_leafwise(grads, params, m_states, key, frac,
                              ltp: LTPConfig, worker_axes, n_workers: int,
                              lr, momentum: float = 0.9):
    """ZeRO-style LTP sync (beyond-paper, §Perf): per-worker packet masking,
    then ``psum_scatter`` in packet space (each worker owns 1/W of the
    packet stream — a sharded PS, like the paper's multi-PS deployment),
    SGD-momentum on the local shard, and a bf16 *delta* all-gather back.

    Ring-volume napkin math vs masked psum: all-reduce(f32 grads) moves
    ~2x bytes; RS(f32) + AG(bf16 delta) moves ~1.5x -> -25% collective
    traffic, and momentum lives sharded (1/W of the f32 state per device).

    m_states: list of (n_pkts_padW / W, p) f32 LOCAL shards (one per leaf,
    sharded over the worker axes on dim 0 at the shard_map boundary).
    Returns (delta_shards [param-dtype packet buffers, worker-sharded],
    new_m_states, realized) — the caller applies deltas outside the manual
    region.
    """
    widx = jnp.zeros((), jnp.int32)
    for a in worker_axes:
        widx = widx * jax.lax.axis_size(a) + jax.lax.axis_index(a)
    k = jax.random.fold_in(key, widx)
    p = ltp.packet_floats
    g_leaves, treedef = jax.tree_util.tree_flatten(grads)
    p_leaves = jax.tree_util.tree_leaves(params)
    new_params, new_m = [], []
    realized = None
    for i, (gleaf, pleaf) in enumerate(zip(g_leaves, p_leaves)):
        m = _leaf_packet_mask(i, gleaf.shape, k, frac[widx], ltp)
        view = _as_packets(gleaf, p)
        n_pkts = view.shape[0]
        padw = (-n_pkts) % n_workers
        if padw:
            view = jnp.concatenate(
                [view, jnp.zeros((padw, p), jnp.float32)])
            m = jnp.concatenate([m, jnp.zeros((padw,), jnp.float32)])
        masked = view * m[:, None]
        shard = jax.lax.psum_scatter(
            masked, worker_axes, scatter_dimension=0, tiled=True)
        if ltp.compensation == "count":
            cnt = jax.lax.psum_scatter(
                m, worker_axes, scatter_dimension=0, tiled=True)
            shard = shard / jnp.maximum(cnt, 1.0)[:, None]
        else:
            shard = shard / n_workers
        m_new = momentum * m_states[i] + shard
        delta = (-lr * m_new).astype(pleaf.dtype)
        # the bf16 delta leaves the manual region as a worker-sharded
        # packet buffer; the all-gather back to replicated params happens
        # in GSPMD auto land (outside), where reshapes of gathered values
        # are unrestricted
        new_params.append(delta)
        new_m.append(m_new)
        if realized is None:
            realized = jax.lax.psum(jnp.mean(m), worker_axes) / n_workers
    return new_params, new_m, realized


def zero_momentum_shapes(params_shape, ltp: LTPConfig, n_workers: int):
    """Global shapes of the packet-space momentum buffers (sharded over
    the worker axes on dim 0)."""
    out = []
    for leaf in jax.tree_util.tree_leaves(params_shape):
        size = 1
        for s in leaf.shape:
            size *= s
        n_pkts = max(1, -(-size // ltp.packet_floats))
        n_pkts += (-n_pkts) % n_workers
        out.append(jax.ShapeDtypeStruct((n_pkts, ltp.packet_floats),
                                        jnp.float32))
    return out


def make_ltp_sync(params_shape, mesh, ltp: LTPConfig, grad_specs) -> LTPSync:
    """Build an LTPSync from a params shape-pytree and its sharding specs."""
    plan = pk.local_plan(
        params_shape, grad_specs, mesh,
        packet_floats=ltp.packet_floats,
        critical_per_tensor=ltp.critical_per_tensor,
    )
    dp = dp_axes(mesh)
    w = 1
    for a in dp:
        w *= mesh.shape[a]
    return LTPSync(mesh=mesh, plan=plan, ltp=ltp, grad_specs=grad_specs, n_workers=w)
