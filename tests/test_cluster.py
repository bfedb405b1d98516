"""Multi-edge topology model (paper §3.1) + temporal events (§2.2)."""

import math

import pytest

from repro.core import (DEVICE_PROFILES, ClusterTopology, DeviceInstance,
                        Edge, MultiEdgeLink, NetworkEvent, dgx_h100_node,
                        hetero_cluster, homogeneous_cluster, multi_pod_tpu,
                        tpu_pod)


def test_multi_edge_best_and_aggregate():
    link = MultiEdgeLink(0, 1, [
        Edge(450e9, 1e-6, "nvlink", ("pcie",)),
        Edge(16e9, 5e-6, "pcie", ("nvlink",)),
        Edge(50e9, 1e-6, "ici-x"),
    ])
    # big transfer: nvlink wins
    assert link.best_edge(1 << 30).tag == "nvlink"
    # conflicting edges share one class; independent edges add
    agg = link.aggregate_bandwidth()
    assert agg == pytest.approx(450e9 + 50e9)


def test_unequal_bandwidth_dgx(paper_fig="5a"):
    topo = dgx_h100_node()
    # pairs touching GPU 0/7 have the extra NVSwitch edge
    assert len(topo.link(0, 3).edges) == 3
    assert len(topo.link(2, 3).edges) == 2


def test_tpu_torus_multi_edge_axes():
    topo = tpu_pod(16, torus=(4, 4))
    # each chip connects along both torus axes with distinct edge classes
    tags = {e.tag for link in topo.links.values() for e in link.edges}
    assert tags == {"ici-x", "ici-y"}


def test_multi_pod_has_slow_dci():
    topo = multi_pod_tpu(pods=2, chips_per_pod=16)
    dci = [e for link in topo.links.values() for e in link.edges
           if e.tag == "dci"]
    assert len(dci) == 16
    assert all(e.bandwidth < 50e9 for e in dci)


def test_events_and_snapshot_isolation():
    topo = homogeneous_cluster(4, "V100", gpus_per_node=4)
    topo.events = [NetworkEvent(5.0, "bandwidth", factor=0.25,
                                selector="nvlink"),
                   NetworkEvent(9.0, "fail", device_id=3)]
    snap4 = topo.snapshot(4.0)
    snap6 = topo.snapshot(6.0)
    snap10 = topo.snapshot(10.0)
    bw = lambda t: t.link(0, 1).edges[0].effective_bandwidth
    assert bw(snap6) == pytest.approx(0.25 * bw(snap4))
    assert len(snap10.alive_ids()) == 3
    # snapshots never mutate the base topology
    assert len(topo.alive_ids()) == 4
    assert bw(topo.snapshot(0.0)) == bw(snap4)


def test_hetero_cluster_types_and_intra_bw():
    topo = hetero_cluster({"RTX4090D": 4, "V100": 4}, gpus_per_node=4)
    assert topo.is_heterogeneous()
    assert sorted(topo.device_types()) == ["RTX4090D", "V100"]
    # consumer card nodes are PCIe-only; V100 nodes have NVLink
    tags_ada = {e.tag for e in topo.link(0, 1).edges}
    tags_v = {e.tag for e in topo.link(4, 5).edges}
    assert tags_ada == {"pcie"}
    assert "nvlink" in tags_v


def test_apply_event_snapshot_roundtrip_all_kinds():
    """All four event kinds round-trip through apply_event/snapshot,
    including a join that revives a failed device."""
    topo = homogeneous_cluster(4, "V100", gpus_per_node=4)
    topo.events = [
        NetworkEvent(1.0, "bandwidth", factor=0.5, selector="nvlink"),
        NetworkEvent(2.0, "slowdown", device_id=1, factor=0.4),
        NetworkEvent(3.0, "fail", device_id=2),
        NetworkEvent(4.0, "join", device_id=2, factor=0.8),
    ]
    s1 = topo.snapshot(1.5)
    assert s1.link(0, 1).edges[0].bw_factor == pytest.approx(0.5)
    s2 = topo.snapshot(2.5)
    assert s2.device(1).perf_factor == pytest.approx(0.4)
    s3 = topo.snapshot(3.5)
    assert s3.alive_ids() == [0, 1, 3]
    s4 = topo.snapshot(4.5)
    assert s4.alive_ids() == [0, 1, 2, 3]          # join after fail revives
    assert s4.device(2).perf_factor == pytest.approx(0.8)
    # earlier state still reconstructable after later queries
    assert topo.snapshot(0.5).device(1).perf_factor == 1.0


def test_unknown_event_kind_and_mode_raise():
    topo = homogeneous_cluster(2, "V100", gpus_per_node=2)
    with pytest.raises(ValueError, match="unknown event kind"):
        topo.apply_event(NetworkEvent(0.0, "meteor", device_id=0))
    with pytest.raises(ValueError, match="unknown event mode"):
        topo.apply_event(NetworkEvent(0.0, "bandwidth", factor=0.5,
                                      mode="wobble"))


def test_scale_mode_composes_and_restores():
    """Overlapping scale-mode events multiply; reciprocal factors restore
    the previous level exactly (the congestion-burst contract).  Set-mode
    events remain absolute."""
    topo = homogeneous_cluster(4, "V100", gpus_per_node=2)
    e = topo.link(0, 1).edges[0]
    topo.apply_event(NetworkEvent(1.0, "bandwidth", factor=0.5,
                                  selector=e.tag, mode="scale"))
    topo.apply_event(NetworkEvent(2.0, "bandwidth", factor=0.5,
                                  selector=e.tag, mode="scale"))
    assert e.bw_factor == pytest.approx(0.25)       # bursts compose
    topo.apply_event(NetworkEvent(3.0, "bandwidth", factor=2.0,
                                  selector=e.tag, mode="scale"))
    topo.apply_event(NetworkEvent(4.0, "bandwidth", factor=2.0,
                                  selector=e.tag, mode="scale"))
    assert e.bw_factor == pytest.approx(1.0)        # full restore
    topo.apply_event(NetworkEvent(5.0, "bandwidth", factor=0.3,
                                  selector=e.tag, mode="set"))
    topo.apply_event(NetworkEvent(6.0, "bandwidth", factor=0.7,
                                  selector=e.tag, mode="set"))
    assert e.bw_factor == pytest.approx(0.7)        # set stays absolute
    # slowdown composes the same way
    topo.apply_event(NetworkEvent(7.0, "slowdown", device_id=0, factor=0.5,
                                  mode="scale"))
    topo.apply_event(NetworkEvent(8.0, "slowdown", device_id=0, factor=0.5,
                                  mode="scale"))
    assert topo.device(0).perf_factor == pytest.approx(0.25)


def test_snapshot_incremental_cache_matches_full_replay():
    """The incremental snapshot cache must be invisible: any query order
    matches a from-scratch replay, and base-topology mutations invalidate."""
    def fresh():
        t = homogeneous_cluster(4, "V100", gpus_per_node=4)
        t.events = [NetworkEvent(float(i), "bandwidth",
                                 factor=0.9 ** (i % 5 + 1),
                                 selector="nvlink", mode="set")
                    for i in range(1, 40)] + \
                   [NetworkEvent(10.5, "slowdown", device_id=1, factor=0.5),
                    NetworkEvent(20.5, "fail", device_id=3),
                    NetworkEvent(30.5, "join", device_id=3)]
        return t

    def state(t):
        return ([(d.device_id, d.alive, d.perf_factor)
                 for d in t.devices.values()],
                [(k, e.tag, e.bw_factor) for k, link in sorted(t.links.items())
                 for e in link.edges])

    inc = fresh()
    for t in (0.0, 5.0, 10.7, 20.7, 25.0, 30.7, 39.0, 12.0, 39.0):
        assert state(inc.snapshot(t)) == state(fresh().snapshot(t)), t
    # mutating the base invalidates the cache
    inc.apply_event(NetworkEvent(0.0, "slowdown", device_id=0, factor=0.25))
    snap = inc.snapshot(5.0)
    assert snap.device(0).perf_factor == pytest.approx(0.25)


def test_roofline_eq1_regimes():
    spec = DEVICE_PROFILES["V100"]
    # compute-bound: huge flops, tiny traffic
    t_c = spec.roofline_time(1e15, 1e6)
    assert t_c == pytest.approx(1e15 / (spec.peak_flops * spec.matmul_eff))
    # memory-bound: tiny flops, huge traffic
    t_m = spec.roofline_time(1e6, 1e12)
    assert t_m == pytest.approx(1e12 / spec.hbm_bw)


def test_device_kind_maps_to_profile_and_unknown_kind_is_an_error():
    from repro.core import profile_for_device_kind
    assert profile_for_device_kind("TPU v5 lite") == "TPUv5e"
    assert "TPUv5e" in DEVICE_PROFILES
    with pytest.raises(KeyError, match="no device profile"):
        profile_for_device_kind("cpu")
