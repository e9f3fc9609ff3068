"""Host profiler spans of the runtime (DESIGN.md §12, "Host spans"):
one ``ltp.sim.run`` a run, and one ``ltp.masks``, ``ltp.step.inputs``
and ``ltp.step.dispatch`` a bsp round, all inside it and carrying the
iteration; one dispatch a gradient and one an apply under async; and a
seeded history bitwise the same with the profiler on."""
import glob

import jax
import pytest
from jax.profiler import ProfileData

from repro.config import LTPConfig, NetConfig, TrainConfig
from repro.configs import get_config
from repro.data import SyntheticCIFAR, batches
from repro.models import build
from repro.obs import spans
from repro.optim import make_optimizer
from repro.runtime import ClusterRuntime

W = 4
STEPS = 3
NET = NetConfig(10, 1, 0.001, 4096)
NAMES = (spans.SIM_RUN, spans.MASKS, spans.STEP_INPUTS, spans.STEP_DISPATCH)
MODES = [("bsp", "des"), ("bsp", "analytic"), ("async", "des")]


def _run(api, policy, transport, seed=7):
    tc = TrainConfig(batch=4 * W, lr=0.05, steps=STEPS)
    rt = ClusterRuntime(api, make_optimizer(tc), tc, LTPConfig(), NET,
                        n_workers=W, policy=policy, transport=transport,
                        seed=seed)
    rt.run(batches(SyntheticCIFAR(seed=3), 4 * W, STEPS))
    return rt


def _spans(log_dir):
    """The runtime's spans on the host planes: name -> [(start, end,
    stats)], by the name's part before any '#'."""
    (path,) = glob.glob(f"{log_dir}/plugins/profile/*/*.xplane.pb")
    found = {n: [] for n in NAMES}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                name = e.name.split("#", 1)[0]
                if name in found:
                    found[name].append((e.start_ns, e.start_ns + e.duration_ns,
                                        {k: v for k, v in e.stats}))
    return found


@pytest.fixture(scope="module")
def api():
    return build(get_config("papernet").replace(d_model=8, n_layers=3))


@pytest.fixture(scope="module")
def plain(api):
    """Each mode run without the profiler; this also compiles its
    programs, so the traced runs below trace no compile."""
    return {mode: _run(api, *mode) for mode in MODES}


@pytest.fixture(scope="module")
def traced(api, plain, tmp_path_factory):
    out = {}
    for mode in MODES:
        log_dir = tmp_path_factory.mktemp("-".join(mode))
        with jax.profiler.trace(str(log_dir)):
            rt = _run(api, *mode)
        out[mode] = (rt, _spans(log_dir))
    return out


@pytest.mark.parametrize("transport", ["des", "analytic"])
def test_bsp_opens_each_span_once_a_round_inside_the_run(traced, transport):
    rt, found = traced[("bsp", transport)]
    assert len(rt.history) == STEPS
    (run,) = found[spans.SIM_RUN]
    for name in NAMES[1:]:
        evs = found[name]
        assert sorted(s["iteration"] for _, _, s in evs) == list(range(STEPS))
        assert all(run[0] <= t0 <= t1 <= run[1] for t0, t1, _ in evs)
    packets = W * rt.plan.n_packets
    assert all(s["packets"] == packets for *_, s in found[spans.MASKS])
    # images and labels, the masks, the fractions and the learning rate
    batch_bytes = 4 * W * (32 * 32 * 3 * 4 + 4)
    assert all(s["bytes"] == batch_bytes + 4 * packets + 4 * W + 4
               for *_, s in found[spans.STEP_INPUTS])


def test_async_dispatches_once_a_gradient_and_once_an_apply(traced):
    rt, found = traced[("async", "des")]
    grads = sum(rec["n_grads"] for rec in rt.history)
    assert grads == W * STEPS
    dispatch = found[spans.STEP_DISPATCH]
    assert len(dispatch) == grads + len(rt.history)
    assert all("iteration" in s for *_, s in dispatch)
    assert len(found[spans.MASKS]) == grads
    (run,) = found[spans.SIM_RUN]
    assert all(run[0] <= t0 <= t1 <= run[1] for t0, t1, _ in dispatch)


@pytest.mark.parametrize("mode", MODES, ids="-".join)
def test_history_is_bitwise_the_same_with_the_profiler_on(plain, traced,
                                                          mode):
    rt, _ = traced[mode]
    assert rt.history == plain[mode].history
    assert rt.tel.events == plain[mode].tel.events
