"""Topology-first construction surface (DESIGN.md §11).

Pins the builder API: ``flat``/``multi_ps``/``rack_spine`` validation,
the rack-grid geometry helpers, the attainable-share math that seeds the
Early-Close LT thresholds, the one ``resolve_topology`` rule every entry
point routes through, and the deprecation shims for the old construction
kwargs (``n_ps=`` / ``spec=``).
"""
import dataclasses

import numpy as np
import pytest

from repro.config import LTPConfig, NetConfig, RuntimeConfig
from repro.net.simcore import Sim
from repro.net.topology import (
    APIDeprecationWarning,
    GatherSpec,
    Topology,
    as_topology,
    flat,
    multi_ps,
    rack_spine,
    resolve_topology,
)
from repro.runtime.transport import DESTransport

NET = NetConfig(10, 1, 0.001, 4096)
BW = NET.bandwidth_gbps * 1e9


# ---------------------------------------------------------------------------
# builders
# ---------------------------------------------------------------------------


def test_flat_builder():
    t = flat()
    assert isinstance(t, Topology) and isinstance(t, GatherSpec)
    assert t.n_ps == 1 and not t.hierarchical and t.name == "flat"
    assert t.n_workers is None
    t4 = flat(n_ps=4)
    assert t4.n_ps == 4 and t4.name == "flat_ps4"
    with pytest.raises(ValueError, match="n_ps"):
        flat(n_ps=0)


def test_multi_ps_is_flat_sharded():
    t = multi_ps(8)
    assert t.n_ps == 8 and not t.hierarchical


def test_rack_spine_builder_and_geometry():
    t = rack_spine(4, 8, oversub=4.0, n_ps=2, ps_racks=(0, 3))
    assert t.hierarchical and t.n_workers == 32
    assert t.name == "rack4x8_agg_os4"
    assert t.rack_of(0) == 0 and t.rack_of(7) == 0 and t.rack_of(8) == 1
    assert t.rack_members(3) == list(range(24, 32))
    assert t.ps_rack(0) == 0 and t.ps_rack(1) == 3
    assert t.uplink_bps(NET) == pytest.approx(8 * BW / 4.0)
    t.validate_workers(32)
    with pytest.raises(ValueError, match="rack grid"):
        t.validate_workers(16, "caller")
    noagg = rack_spine(2, 4, agg=False)
    assert not noagg.inetwork_agg and noagg.name == "rack2x4_os4"
    assert noagg.ps_rack(0) is None


def test_rack_spine_validation():
    with pytest.raises(ValueError, match="positive"):
        rack_spine(0, 8)
    with pytest.raises(ValueError, match="positive"):
        rack_spine(4, 0)
    with pytest.raises(ValueError, match="oversub"):
        rack_spine(4, 8, oversub=0.0)
    with pytest.raises(ValueError, match="n_ps"):
        rack_spine(4, 8, n_ps=0)
    with pytest.raises(ValueError, match="per shard"):
        rack_spine(4, 8, n_ps=2, ps_racks=(0,))
    with pytest.raises(ValueError, match="out of range"):
        rack_spine(4, 8, n_ps=1, ps_racks=(4,))


# ---------------------------------------------------------------------------
# attainable-share math (feeds the LT init formula)
# ---------------------------------------------------------------------------


def test_worker_share_flat_matches_fair_share():
    assert flat().worker_share_bps(0, 16, NET) == pytest.approx(BW / 16)


def test_worker_share_rack_no_agg_pays_uplink_split():
    t = rack_spine(4, 8, oversub=4.0, n_ps=2, agg=False)
    up = t.uplink_bps(NET)
    expect = min(BW / 32, up / (8 * 2))
    assert t.worker_share_bps(5, 32, NET) == pytest.approx(expect)


def test_worker_share_rack_agg_rides_merged_flow():
    t = rack_spine(4, 8, oversub=4.0, n_ps=2, agg=True)
    expect = min(t.uplink_bps(NET) / 2, BW / 4)
    assert t.worker_share_bps(5, 32, NET) == pytest.approx(expect)
    # aggregation must never make the modeled share WORSE than per-worker
    noagg = rack_spine(4, 8, oversub=4.0, n_ps=2, agg=False)
    assert (t.worker_share_bps(5, 32, NET)
            >= noagg.worker_share_bps(5, 32, NET))


def test_worker_share_heterogeneous_access_cap():
    mult = np.full(8, 0.1)
    t = flat(worker_rate_mult=mult)
    assert t.heterogeneous
    assert t.worker_share_bps(3, 8, NET) == pytest.approx(BW * 0.1)


# ---------------------------------------------------------------------------
# coercion + resolution rule
# ---------------------------------------------------------------------------


def test_as_topology_copies_spec_fields():
    spec = GatherSpec(n_ps=4, cross_traffic_load=0.5,
                      worker_delay_ms=np.arange(8.0))
    t = as_topology(spec)
    assert isinstance(t, Topology) and not t.hierarchical
    assert t.n_ps == 4 and t.cross_traffic_load == 0.5
    np.testing.assert_array_equal(t.worker_delay_ms, np.arange(8.0))
    # identity on an already-built Topology
    built = rack_spine(2, 4)
    assert as_topology(built) is built


def test_resolve_topology_precedence():
    topo = rack_spine(2, 4)
    assert resolve_topology(topo) is topo
    # default: single-PS flat, no warning
    assert resolve_topology(None).n_ps == 1
    with pytest.raises(ValueError, match="not both"):
        resolve_topology(topo, n_ps=2, owner="X")
    with pytest.raises(ValueError, match="not both"):
        resolve_topology(topo, spec=GatherSpec(), owner="X")


def test_resolve_topology_deprecated_aliases_warn():
    with pytest.warns(APIDeprecationWarning, match="n_ps"):
        t = resolve_topology(None, n_ps=4, owner="X")
    assert t.n_ps == 4
    spec = GatherSpec(n_ps=2)
    with pytest.warns(APIDeprecationWarning, match="spec"):
        t = resolve_topology(None, spec=spec, owner="X")
    assert t.n_ps == 2
    with pytest.warns(APIDeprecationWarning):
        with pytest.raises(ValueError, match="contradicts"):
            resolve_topology(None, spec=spec, n_ps=4, owner="X")


def test_destransport_deprecated_nps_shim():
    with pytest.warns(APIDeprecationWarning, match="DESTransport"):
        tr = DESTransport(Sim(), NET, LTPConfig(), "ltp", 4, 1e5, n_ps=2)
    assert tr.n_ps == 2
    # new spelling: silent
    tr = DESTransport(Sim(), NET, LTPConfig(), "ltp", 4, 1e5,
                      topology=multi_ps(2))
    assert tr.n_ps == 2


def test_destransport_rejects_mismatched_rack_grid():
    with pytest.raises(ValueError, match="rack grid"):
        DESTransport(Sim(), NET, LTPConfig(), "ltp", 6, 1e5,
                     topology=rack_spine(2, 4))


# ---------------------------------------------------------------------------
# LTPConfig protocol/runtime split
# ---------------------------------------------------------------------------


def test_ltpconfig_runtime_view():
    ltp = LTPConfig(staleness_comp=0.5, error_feedback=True, seed=9)
    rc = ltp.runtime()
    assert isinstance(rc, RuntimeConfig)
    assert rc.staleness_comp == 0.5 and rc.error_feedback and rc.seed == 9


def test_with_runtime_overlay():
    base = LTPConfig()
    rc = RuntimeConfig(staleness_comp=0.7, sync_backend="jit")
    merged = base.with_runtime(rc)
    assert merged.staleness_comp == 0.7
    assert merged.sync_backend == "jit"
    # the platform decides interpret mode (kernels.common.interpret_mode);
    # neither config carries it
    assert not hasattr(merged, "kernel_interpret")
    assert not hasattr(rc, "kernel_interpret")
    # protocol fields untouched
    assert merged.data_pct_threshold == base.data_pct_threshold
    assert merged.deadline_c_ms == base.deadline_c_ms
    # None -> identity (no silent reset of protocol-side defaults)
    assert base.with_runtime(None) is base
    # every RuntimeConfig field must exist on LTPConfig (the overlay
    # copies by name — a field rename on one side must fail loudly here)
    ltp_fields = {f.name for f in dataclasses.fields(LTPConfig)}
    rc_fields = {f.name for f in dataclasses.fields(RuntimeConfig)}
    assert rc_fields <= ltp_fields
