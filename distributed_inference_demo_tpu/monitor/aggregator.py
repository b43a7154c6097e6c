"""Server side of the monitor round: ROUTER service + report aggregation.

Re-implements the missing ``SecureConnection.monitor.Monitor`` (inferred at
``server.py:849-858``, SURVEY.md §2.2): has ``start()``, an
``is_monitor_ready`` event, and ``get_monitor_info()`` returning per-device
measurements; pushes the peer graph to devices on handshake and tells them
to stop once every expected device has reported (the reference sends
periodic "signal"/"stop" strings, ``MonitorService.kt:186-205``).
"""

from __future__ import annotations

import threading
from typing import Dict, List, Optional

import zmq

from ..control.messages import Envelope, MsgType, make
from ..control.router import RouterService
from ..planner.planner import DeviceProfile

DEFAULT_BANDWIDTH = 1e9       # bytes/sec, assumed when a pair wasn't probed
DEFAULT_LATENCY = 1e-3        # seconds


class MonitorAggregator:
    """Collects per-device reports; ready once all expected devices report."""

    def __init__(self, expected: List[str]):
        self.expected = list(expected)
        self.reports: Dict[str, dict] = {}
        self._lock = threading.Lock()
        self.is_monitor_ready = threading.Event()

    def add_report(self, device_id: str, report: dict) -> None:
        with self._lock:
            self.reports[device_id] = report
            if all(d in self.reports for d in self.expected):
                self.is_monitor_ready.set()

    def get_monitor_info(self) -> Dict[str, dict]:
        with self._lock:
            return dict(self.reports)

    def device_profiles(self, addresses: Dict[str, str],
                        ring_order: Optional[List[str]] = None
                        ) -> List[DeviceProfile]:
        """Fold reports into planner inputs (the ``server.py:858`` tuple).

        ``addresses``: device_id -> data-plane address.  ``ring_order``
        fixes the chain order (defaults to ``expected`` order); each
        device's egress bandwidth/latency is its measurement toward the
        NEXT device in the ring."""
        order = ring_order or self.expected
        info = self.get_monitor_info()
        profiles = []
        for i, dev_id in enumerate(order):
            rep = info.get(dev_id, {})
            nxt = order[(i + 1) % len(order)]
            bw = (rep.get("bandwidth") or {}).get(nxt, DEFAULT_BANDWIDTH)
            lat = (rep.get("latency") or {}).get(nxt, DEFAULT_LATENCY)
            mem = rep.get("memory") or {}
            if not rep.get("flops"):
                raise ValueError(
                    f"device {dev_id!r} reported no flops measurement; "
                    "the planner does not invent a compute rate")
            profiles.append(DeviceProfile(
                device_id=dev_id,
                address=addresses.get(dev_id, ""),
                flops_per_sec=rep["flops"],
                memory_bytes=int(mem.get("available")
                                 or mem.get("total") or (16 << 30)),
                platform=rep.get("platform", "cpu"),
                chips=int(rep.get("chips", 1)),
                egress_bandwidth=bw or DEFAULT_BANDWIDTH,
                egress_latency=lat if lat is not None else DEFAULT_LATENCY,
            ))
        return profiles


class MonitorService(RouterService):
    """ROUTER endpoint the agents talk to (reference port 34567 role)."""

    name = "monitor"

    def __init__(self, aggregator: MonitorAggregator,
                 bind_host: str = "127.0.0.1", port: int = 0,
                 min_rounds: int = 1,
                 ctx: Optional[zmq.Context] = None):
        super().__init__(bind_host=bind_host, port=port, ctx=ctx)
        self.agg = aggregator
        self.min_rounds = min_rounds
        # device_id -> {host, bw_port} gathered from hellos
        self._peers: Dict[str, dict] = {}
        self._rounds: Dict[str, int] = {}
        self._lock = threading.Lock()

    def _peer_graph(self, dev_id: str) -> Dict[str, dict]:
        with self._lock:
            return {k: dict(v) for k, v in self._peers.items()
                    if k != dev_id}

    def handle(self, dev_id: str, msg: Envelope) -> List[bytes]:
        if msg.type == MsgType.MONITOR_HELLO:
            with self._lock:
                self._peers[dev_id] = {
                    "host": msg.get("host", "127.0.0.1"),
                    "bw_port": msg.get("bw_port", 0),
                }
            return [make(MsgType.MONITOR_GRAPH,
                         peers=self._peer_graph(dev_id))]
        if msg.type == MsgType.MONITOR_REPORT:
            self.agg.add_report(dev_id, msg.get("report", {}))
            with self._lock:
                self._rounds[dev_id] = self._rounds.get(dev_id, 0) + 1
                done = (self.agg.is_monitor_ready.is_set()
                        and self._rounds[dev_id] >= self.min_rounds)
            if done:
                return [make(MsgType.MONITOR_STOP)]
            # refresh the peer graph with anyone who joined since
            return [make(MsgType.MONITOR_GRAPH,
                         peers=self._peer_graph(dev_id))]
        return [make(MsgType.ERROR, reason=f"unexpected {msg.type.value}")]
