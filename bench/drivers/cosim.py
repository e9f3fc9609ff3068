"""The co-simulated PS training loop under the bsp policy:
``ClusterRuntime`` (which ``PSTrainer`` fronts) with W workers and one
PS, the network simulated by the DES or the analytic transport, the
fused training step on the chip.

Set-up builds the model and the optimizer once and makes the weights
from the seed. A first runtime, started from those weights, runs the
checked steps through the very jitted program the window calls (the
runtime's step cache is keyed on the model and optimizer objects, so
every later runtime finds it) and records what the reference needs:
the delivery masks the transport drew. The window is one continuous
``run`` of a second runtime over the state the set-up left, ended by
``block_until_ready`` on the parameters. Its iteration count is fixed by
the traffic file (``iterations_per_s`` times ``--seconds``), so every
run of one seed does the same work.

``ClusterRuntime`` is driven directly and not through ``PSTrainer``:
the façade forwards the same arguments but has no way to take the
benchmark's weights and the carried state.
"""
from __future__ import annotations

import gc
import time
from typing import Callable, Dict, List

import numpy as np

from bench import harness
from bench.harness import load_module

#: steps compared with the reference
CHECKED = 3


def leaf_norms(tree) -> List[float]:
    import jax

    return [float(np.linalg.norm(np.asarray(x, np.float64)))
            for x in jax.tree_util.tree_leaves(tree)]


def first_grad_norms(opt_state) -> List[float]:
    """The first gradient as the optimizer got it, from its state after
    one step: SGD's momentum buffer is the gradient itself."""
    return leaf_norms(opt_state["m"])


class Run:
    def __init__(self, cell, seed: int, devs, log: Callable[[str], None]):
        import jax
        from repro.config import LTPConfig, NetConfig, TrainConfig
        from repro.optim import make_optimizer

        self.cell, self.seed, self.log = cell, seed, log
        t = cell.traffic
        self.t = t
        self.w = int(t["workers"])
        self.b = int(t["per_worker_batch"])
        self.policy = t["policy"]
        self.model = cell.model
        self.sizes = cell.config
        self.api = self.model.build(self.sizes)
        self.tc = TrainConfig(batch=self.w * self.b, lr=float(t["lr"]),
                              momentum=float(t["momentum"]), steps=1)
        self.opt = make_optimizer(self.tc)
        self.ltp = LTPConfig(**t["ltp"])
        self.net = NetConfig(**t["net"])
        traffic = load_module(harness.BENCH / "traffic" / "cifar.py")
        self.pool = traffic.pool(seed, int(t["pool_batches"]),
                                 self.w * self.b)
        self.params0 = self.model.make_params(harness.seed_key(seed),
                                              self.sizes)
        jax.block_until_ready(self.params0)
        self.record: Dict = {}
        self._warm_up()

    # -- the runtime -------------------------------------------------------
    def runtime(self, params, opt_state):
        from repro.runtime import ClusterRuntime

        return ClusterRuntime(
            self.api, self.opt, self.tc, self.ltp, self.net,
            n_workers=self.w, protocol=self.t["protocol"],
            policy=self.policy, compute_time=float(self.t["compute_time_s"]),
            seed=self.seed, transport=self.t["transport"],
            params=params, opt_state=opt_state)

    def _batches(self, n: int, start: int = 0):
        return [self.pool[(start + i) % len(self.pool)] for i in range(n)]

    # -- set-up ------------------------------------------------------------
    def _warm_up(self) -> None:
        """The checked steps, through the window's own program."""
        import jax
        from repro.runtime import step as stp

        rt = self.runtime(self.params0, self.opt.init(self.params0))
        fused = stp.build_fused_step(self.api, self.opt, rt.ltp, rt.plan,
                                     self.w, rt.protocol)
        steps: List[dict] = []

        def recording(params, opt_state, residual, batch, masks, frac, lr):
            out = fused(params, opt_state, residual, batch, masks, frac, lr)
            if len(steps) < CHECKED:
                steps.append({"masks": np.asarray(masks), "lr": float(lr),
                              "opt_state": out[1], "params": out[0]})
            return out

        rt._fused_step = recording
        rt.run(self._batches(CHECKED))
        jax.block_until_ready(rt.params)
        self.record["steps"] = [{"batch": i, "masks": st["masks"],
                                 "lr": st["lr"]}
                                for i, st in enumerate(steps)]
        self.record["prog"] = {
            "losses": [float(h["loss"]) for h in rt.history[:CHECKED]],
            "grad_norms": first_grad_norms(steps[0]["opt_state"]),
            "delta_norms": leaf_norms(jax.tree.map(
                lambda a, b: a - b, steps[-1]["params"], self.params0)),
        }
        self.state = (rt.params, rt.opt_state)

    # -- the window --------------------------------------------------------
    def window(self, seconds: float) -> dict:
        import jax
        from repro.net.simcore import PERF

        iterations = max(1, round(seconds * float(self.t["iterations_per_s"])))
        rt = self.runtime(*self.state)
        batches = self._batches(iterations, CHECKED)
        p0 = PERF.packets
        host0 = harness.HostClock()
        t0 = time.perf_counter()
        rt.run(batches)
        jax.block_until_ready(rt.params)
        wall = time.perf_counter() - t0
        host = host0.since()
        packets = PERF.packets - p0
        done = len(rt.history)
        samples = done * self.w * self.b
        self.state = None
        del rt
        return {"attempted": iterations, "failed": iterations - done,
                "e2e": {"samples_per_s": samples / wall},
                "wall_s": wall, "samples": samples,
                "global_batches": done,
                "packets": packets,
                "flops_per_sample": self.model.flops_per_sample(self.sizes),
                "workers": self.w, "n_floats": self._n_floats(),
                "packet_floats": self.ltp.packet_floats, "host": host}

    def _n_floats(self) -> int:
        import jax

        return sum(int(np.prod(x.shape))
                   for x in jax.tree_util.tree_leaves(self.params0))

    def release(self) -> None:
        self.state = None
        gc.collect()

    # -- the comparison ----------------------------------------------------
    def compare(self, variants=("prog",)) -> Dict[str, Dict[str, float]]:
        """Readings against the reference: of the program ("prog"), and of
        the control and the faults put in its place."""
        ref = reference(self, "ref")
        return {v: harness.training_readings(
                    self.record["prog"] if v == "prog" else reference(self, v),
                    ref)
                for v in variants}

    def readings(self) -> Dict[str, float]:
        return self.compare()["prog"]


# ----------------------------------------------------------------------------
# the plain reference, and the control and faults put in the program's place
# ----------------------------------------------------------------------------

#: the control and the faults this kind of cell can have
FAULTS = ("control", "half_batch", "no_exchange", "unchanged")


def reference(run: Run, variant: str = "ref") -> dict:
    """Follow the checked steps with the plain reference from the same
    weights, batches and delivery masks. ``variant``: "ref" in float32 at
    the highest matmul precision; "control" the same with its products
    one precision lower, in three bfloat16 passes (JAX's "high"); the
    faults "half_batch" (each worker's gradient over half its rows),
    "no_exchange" (the PS applies worker 0's gradient alone) and
    "unchanged" (the step returns its state as it got it). The workers'
    gradients of a step are taken in one call, mapped over the workers."""
    import jax
    import jax.numpy as jnp

    ref = load_module(harness.BENCH / "configs"
                      / f"{run.cell.config_name}_ref.py")
    sizes, w, b = run.sizes, run.w, run.b
    prec = "bf16_3x" if variant == "control" else "highest"
    rows = b // 2 if variant == "half_batch" else b
    pf = run.ltp.packet_floats

    grads = jax.jit(jax.vmap(jax.value_and_grad(
        lambda p, x, y: ref.loss(p, x, y, sizes, precision=prec)),
        in_axes=(None, 0, 0)))
    p0 = run.params0
    treedef = jax.tree_util.tree_structure(p0)
    shapes = [x.shape for x in jax.tree_util.tree_leaves(p0)]
    sizes_ = [int(np.prod(s)) for s in shapes]
    n_floats = sum(sizes_)
    n_pk = -(-n_floats // pf)

    @jax.jit
    def reduce(g_w, masks):
        """The workers' gradients as packet streams, masked, summed over
        the workers and divided by W, back in the parameters' shapes."""
        flat = jnp.concatenate([x.reshape(w, -1) for x in
                                jax.tree_util.tree_leaves(g_w)], axis=1)
        flat = jnp.pad(flat, ((0, 0), (0, n_pk * pf - n_floats)))
        tot = jnp.sum(flat.reshape(w, n_pk, pf) * masks[:, :, None], axis=0)
        flat, out, off = tot.reshape(-1) / w, [], 0
        for shp, n in zip(shapes, sizes_):
            out.append(flat[off:off + n].reshape(shp))
            off += n
        return jax.tree_util.tree_unflatten(treedef, out)

    @jax.jit
    def sgdm(params, m, g, lr):
        m = jax.tree.map(lambda m_, g_: run.tc.momentum * m_ + g_, m, g)
        return jax.tree.map(lambda p, m_: p - lr * m_, params, m), m

    params, m = p0, jax.tree.map(jnp.zeros_like, p0)
    losses, grad1 = [], None
    for step in run.record["steps"]:
        batch = run.pool[step["batch"] % len(run.pool)]
        x = batch["images"].reshape((w, b) + batch["images"].shape[1:])
        y = batch["labels"].reshape(w, b)
        loss_w, g_w = grads(params, jnp.asarray(x[:, :rows]),
                            jnp.asarray(y[:, :rows]))
        masks = np.asarray(step["masks"], np.float32)
        if variant == "no_exchange":
            masks = masks * (np.arange(w) == 0)[:, None]
        if variant != "unchanged":
            params, m = sgdm(params, m, reduce(g_w, jnp.asarray(masks)),
                             jnp.asarray(step["lr"], jnp.float32))
        if grad1 is None:
            grad1 = m
        losses.append(float(np.mean(np.asarray(loss_w))))
    return {"losses": losses,
            "grad_norms": leaf_norms(grad1),
            "delta_norms": leaf_norms(jax.tree.map(
                lambda a, b_: a - b_, params, p0))}


def prepare(cell, seed: int, devs, log) -> Run:
    return Run(cell, seed, devs, log)
