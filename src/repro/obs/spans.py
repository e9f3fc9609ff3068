"""Host profiler spans of the runtime (DESIGN.md §12, "Host spans").

``ClusterRuntime`` opens each as a ``jax.profiler.TraceAnnotation``.
They land in the profiler's own trace on the host thread that runs the
co-simulation, on the clock the device ops share, so a trace shows what
the host did while the chip waited. They read no Python clock and draw
no random number: a seeded run is bitwise the same with the profiler on
or off. Each is opened once a round (bsp) or once a gradient or apply
(async/ssp), never per packet, event or ACK: off the profiler a span
costs about a microsecond.

Each name, where it is opened, and the benchmark metric that reads it
(``bench/metrics/``):

``ltp.sim.run``
    ``ClusterRuntime.run`` around the event loop; every host cost of a
    run lies inside it. Its self time (minus the spans below) is the
    DES's: ``des_self_ms``, and ``idle_in_des_share`` where the chip is
    idle meanwhile.
``ltp.masks``
    a round's delivery masks (bsp: the DES shards' masks tiled onto the
    packet plan, or the analytic gather draw and the Early-Close
    controller) or one gradient's mask row (async/ssp). Stats
    ``iteration``, ``packets``. Read by ``mask_host_ms``.
``ltp.step.inputs``
    the step's host-to-device inputs: the batch, masks, fractions and
    learning rate. Stats ``iteration``, ``bytes``. Read by
    ``step_host_ms``.
``ltp.step.dispatch``
    the call of a jitted program (fused step, worker gradient, apply,
    error-feedback gate). It ends once the call is enqueued, so a long
    one means the call waited. Stat ``iteration``. Read by
    ``step_host_ms``.
"""

SIM_RUN = "ltp.sim.run"
MASKS = "ltp.masks"
STEP_INPUTS = "ltp.step.inputs"
STEP_DISPATCH = "ltp.step.dispatch"
