"""Monitor subsystem tests: probes, aggregation, full agent round over
localhost sockets."""

import pytest

from distributed_inference_demo_tpu.monitor import (
    BandwidthServer, MonitorAgent, MonitorAggregator, MonitorService,
    bandwidth_probe, flops_probe, memory_info, tcp_latency_probe)


# ------------------------------------------------------------------ probes

def test_memory_info_sane():
    mem = memory_info()
    assert mem["total"] > (1 << 30)          # >1 GB host
    assert 0 < mem["available"] <= mem["total"]


def test_flops_probe_positive():
    flops = flops_probe(size=256, warmups=1)
    assert flops > 1e8                        # any real machine beats this


def test_bandwidth_probe_localhost():
    srv = BandwidthServer()
    srv.start()
    try:
        bw = bandwidth_probe("127.0.0.1", srv.port, duration=0.05)
        assert bw is not None and bw > 1e6    # loopback >> 1 MB/s
        lat = tcp_latency_probe("127.0.0.1", srv.port)
        assert lat is not None and lat < 0.5
    finally:
        srv.stop()


def test_latency_probe_unreachable():
    assert tcp_latency_probe("127.0.0.1", 1, attempts=1, timeout=0.2) is None
    assert bandwidth_probe("127.0.0.1", 1, timeout=0.2) is None


# ------------------------------------------------------------- aggregation

def test_aggregator_ready_and_profiles():
    agg = MonitorAggregator(["d0", "d1"])
    agg.add_report("d0", {
        "latency": {"d1": 0.002}, "bandwidth": {"d1": 5e8},
        "memory": {"total": 32 << 30, "available": 8 << 30},
        "flops": 2e12, "platform": "cpu", "chips": 1})
    assert not agg.is_monitor_ready.is_set()
    agg.add_report("d1", {
        "latency": {"d0": 0.003}, "bandwidth": {"d0": 4e8},
        "memory": {"total": 16 << 30, "available": 4 << 30},
        "flops": 9e13, "platform": "tpu", "chips": 8})
    assert agg.is_monitor_ready.is_set()

    profs = agg.device_profiles({"d0": "a:1", "d1": "b:2"})
    assert profs[0].device_id == "d0"
    assert profs[0].flops_per_sec == 2e12
    assert profs[0].memory_bytes == 8 << 30   # planner uses available
    assert profs[0].egress_bandwidth == 5e8   # toward next in ring (d1)
    assert profs[1].platform == "tpu" and profs[1].chips == 8
    assert profs[1].egress_bandwidth == 4e8   # ring wraps d1 -> d0


def test_aggregator_defaults_for_missing_measurements():
    """Link and memory measurements fall back to documented defaults; a
    compute rate nobody measured is refused, not invented."""
    agg = MonitorAggregator(["d0"])
    agg.add_report("d0", {"flops": 3e11})
    p = agg.device_profiles({"d0": "a:1"})[0]
    assert p.flops_per_sec == 3e11 and p.memory_bytes > 0
    assert p.egress_bandwidth > 0
    agg.add_report("d0", {})
    with pytest.raises(ValueError, match="no flops measurement"):
        agg.device_profiles({"d0": "a:1"})


# ------------------------------------------------- end-to-end monitor round

def test_monitor_round_end_to_end():
    agg = MonitorAggregator(["dev-a", "dev-b"])
    svc = MonitorService(agg)
    svc.start()
    agents = [
        MonitorAgent(svc.address, "dev-a", measure_flops=False,
                     bandwidth_duration=0.03),
        MonitorAgent(svc.address, "dev-b", measure_flops=False,
                     bandwidth_duration=0.03),
    ]
    try:
        threads = [a.run_async(max_rounds=10) for a in agents]
        assert agg.is_monitor_ready.wait(timeout=20)
        for t in threads:
            t.join(timeout=20)
            assert not t.is_alive()
        info = agg.get_monitor_info()
        assert set(info) == {"dev-a", "dev-b"}
        for rep in info.values():
            assert rep["memory"]["total"] > 0
        # at least one direction measured real localhost bandwidth
        assert any(rep["bandwidth"] for rep in info.values())
    finally:
        for a in agents:
            a.close()
        svc.stop()
