"""Prefix-aware replicated serving gateway (docs/DESIGN.md §16).

A standalone process (``cli.py gateway``) spreading ``/generate``
traffic across N independent engine replicas, each a full
``runtime/http_server.py`` stack.  Three pieces:

- :class:`ReplicaRegistry` — health-checked membership with
  sustain+cooldown debounce (``registry.py``);
- :class:`PrefixAwareRouter` — cache-aware routing from the gateway's
  own routing history, rendezvous-hash-with-bounded-load fallback
  (``router.py``);
- :class:`GatewayHTTPServer` — the HTTP process and streaming proxy
  with retry-before-first-token (``server.py``).

The gateway holds no engine and never initialises a JAX backend: its own
modules import only the telemetry layer and ``runtime/overload.py``.
Importing this package does pull ``jax`` in (through ``runtime/__init__``),
but an import holds no device — ``chip_smoke.py`` starts the gateway
FIRST, in the replica's own ``JAX_PLATFORMS=tpu`` environment, and the
replica still gets the chip.
"""

from .registry import Replica, ReplicaRegistry, http_stats_prober
from .router import PrefixAwareRouter, RouteDecision
from .server import GatewayHTTPServer

__all__ = [
    "Replica",
    "ReplicaRegistry",
    "http_stats_prober",
    "PrefixAwareRouter",
    "RouteDecision",
    "GatewayHTTPServer",
]
