"""Device assignment: round-robin baseline + bottleneck-minimizing optimizer.

The reference planned with ``round_robin_module_arrangement``
(``server.py:893-905``) — an even split ignoring device speed — and left its
cost-model LP ``Optimizer`` commented out (``server.py:879-891``,
``init_server.py:219-232``).  This module provides both:

- ``round_robin_plan``: the even split, for parity and as a fallback;
- ``plan_partition``: dynamic programming over contiguous layer cuts along
  the fixed ring order (header first, tail last — the order the device pool
  allocates, ``server.py:261-267``), minimizing the pipeline bottleneck
  ``max_i(compute_i + comm_i)`` subject to a 0.7 memory-headroom constraint
  per device (``server.py:860-862``).  Inputs are the analytic model costs
  (cost_model.py) and per-device monitor measurements (flops/s, memory,
  p2p bandwidth/latency — the tuple of ``server.py:858``).

Plans are cacheable to JSON, mirroring the reference's ``ip_module.json`` /
``session.json`` reload path (``server.py:805-820``).
"""

from __future__ import annotations

import json
import os
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence

from ..models.base import ModelConfig, StageSpec
from .cost_model import ModelCostProfile, model_cost_profile

MEMORY_HEADROOM = 0.7  # reference server.py:860-862


class PlanError(RuntimeError):
    """No feasible partition under the given constraints."""


@dataclass(frozen=True)
class DeviceProfile:
    """Planner view of one device (the monitor tuple, ``server.py:858``)."""

    device_id: str
    address: str
    # measured (monitor flops probe) or stated by the operator — never
    # defaulted: a made-up rate would plan layers onto a device nobody
    # timed
    flops_per_sec: float
    memory_bytes: int = 16 << 30
    platform: str = "cpu"              # cpu | tpu
    chips: int = 1                     # TPU chips for intra-stage tp
    # bandwidth to the NEXT device in ring order, bytes/sec; latency sec
    egress_bandwidth: float = 1e9
    egress_latency: float = 1e-3


@dataclass
class StageAssignment:
    device_id: str
    address: str
    layer_start: int
    layer_end: int
    est_compute_sec: float
    est_comm_sec: float
    est_param_bytes: int
    mesh_axes: Dict[str, int] = field(default_factory=dict)

    @property
    def est_step_sec(self) -> float:
        return self.est_compute_sec + self.est_comm_sec


@dataclass
class PartitionPlan:
    model: str
    num_layers: int
    stages: List[StageAssignment]
    est_bottleneck_sec: float
    plan_version: int = 0

    @property
    def stage_ranges(self) -> Dict[str, List[int]]:
        return {s.device_id: [s.layer_start, s.layer_end]
                for s in self.stages}

    @property
    def device_graph(self) -> List[str]:
        return [s.address for s in self.stages]

    @property
    def device_ids(self) -> List[str]:
        return [s.device_id for s in self.stages]

    def stage_specs(self) -> List[StageSpec]:
        return [StageSpec(i, len(self.stages), s.layer_start, s.layer_end)
                for i, s in enumerate(self.stages)]

    def to_json(self) -> dict:
        return {
            "model": self.model, "num_layers": self.num_layers,
            "plan_version": self.plan_version,
            "est_bottleneck_sec": self.est_bottleneck_sec,
            "stages": [{
                "device_id": s.device_id, "address": s.address,
                "layers": [s.layer_start, s.layer_end],
                "est_compute_sec": s.est_compute_sec,
                "est_comm_sec": s.est_comm_sec,
                "est_param_bytes": s.est_param_bytes,
                "mesh_axes": s.mesh_axes,
            } for s in self.stages],
        }

    @staticmethod
    def from_json(d: dict) -> "PartitionPlan":
        return PartitionPlan(
            model=d["model"], num_layers=d["num_layers"],
            plan_version=d.get("plan_version", 0),
            est_bottleneck_sec=d.get("est_bottleneck_sec", 0.0),
            stages=[StageAssignment(
                device_id=s["device_id"], address=s["address"],
                layer_start=s["layers"][0], layer_end=s["layers"][1],
                est_compute_sec=s.get("est_compute_sec", 0.0),
                est_comm_sec=s.get("est_comm_sec", 0.0),
                est_param_bytes=s.get("est_param_bytes", 0),
                mesh_axes=dict(s.get("mesh_axes", {})),
            ) for s in d["stages"]])


def _mesh_axes_for(dev: DeviceProfile) -> Dict[str, int]:
    """TPU stages shard intra-stage over their chips (tp innermost — ICI);
    CPU/edge stages run unsharded (the heterogeneous boundary)."""
    if dev.platform == "tpu" and dev.chips > 1:
        return {"dp": 1, "tp": dev.chips, "sp": 1}
    return {"dp": 1, "tp": 1, "sp": 1}


def _stage_costs(profile: ModelCostProfile, devs: Sequence[DeviceProfile],
                 cfg: ModelConfig, i: int, a: int, b: int, num_devices: int,
                 batch: int, ctx: int):
    """(compute_sec, comm_sec, param_bytes, kv_bytes) for layers [a,b) on
    device i.  TP over a TPU stage's chips divides per-chip FLOPs."""
    dev = devs[i]
    flops = sum(c.flops for c in profile.layers[a:b]) * batch
    params = sum(c.param_bytes for c in profile.layers[a:b])
    kv = sum(c.kv_bytes_per_tok for c in profile.layers[a:b]) * batch * ctx
    if i == 0:
        flops += profile.embed.flops * batch
        params += profile.embed.param_bytes
    if i == num_devices - 1:
        flops += profile.head.flops * batch
        params += profile.head.param_bytes
        if cfg.tie_embeddings and num_devices > 1:
            # slice_stage gives the tail its own copy of the token table
            # for the tied LM head (models/base.py needs_embed) — charge it.
            params += profile.embed.param_bytes
    eff_flops = dev.flops_per_sec * (dev.chips if dev.platform == "tpu"
                                     else 1)
    compute = flops / eff_flops
    if i == num_devices - 1:
        # the tail sends a sampled token id back to the header, not a
        # hidden row
        act = profile.head.act_bytes * batch
    else:
        act = profile.layers[b - 1].act_bytes * batch if b > a else 0
    comm = (dev.egress_latency + act / dev.egress_bandwidth
            if num_devices > 1 else 0.0)
    return compute, comm, params, kv


def plan_partition(cfg: ModelConfig, model_name: str,
                   devices: Sequence[DeviceProfile],
                   batch: int = 1, ctx: Optional[int] = None,
                   profile: Optional[ModelCostProfile] = None,
                   plan_version: int = 0) -> PartitionPlan:
    """Optimal contiguous split along the ring order: minimize the pipeline
    bottleneck, respecting per-device memory headroom.

    DP over (devices used, layers consumed): O(D * L^2)."""
    ctx = ctx or min(cfg.max_seq_len, 1024)
    profile = profile or model_cost_profile(cfg, ctx=ctx)
    L, D = cfg.num_layers, len(devices)
    if D < 1:
        raise PlanError("no devices")
    if D > L:
        raise PlanError(f"more devices ({D}) than layers ({L})")

    def feasible(i, a, b):
        _, _, params, kv = _stage_costs(profile, devices, cfg, i, a, b, D,
                                        batch, ctx)
        return params + kv <= MEMORY_HEADROOM * devices[i].memory_bytes

    def stage_time(i, a, b):
        comp, comm, _, _ = _stage_costs(profile, devices, cfg, i, a, b, D,
                                        batch, ctx)
        return comp + comm

    INF = float("inf")
    # best[i][j]: minimal bottleneck assigning first j layers to devices 0..i-1
    best = [[INF] * (L + 1) for _ in range(D + 1)]
    cut = [[-1] * (L + 1) for _ in range(D + 1)]
    best[0][0] = 0.0
    for i in range(1, D + 1):
        for j in range(i, L + 1):
            for k in range(i - 1, j):   # each device gets >= 1 layer
                if best[i - 1][k] == INF:
                    continue
                if not feasible(i - 1, k, j):
                    continue
                c = max(best[i - 1][k], stage_time(i - 1, k, j))
                if c < best[i][j]:
                    best[i][j] = c
                    cut[i][j] = k
    if best[D][L] == INF:
        raise PlanError(
            f"no feasible partition of {L} layers over {D} devices "
            f"(memory headroom {MEMORY_HEADROOM})")

    bounds = [L]
    j = L
    for i in range(D, 0, -1):
        j = cut[i][j]
        bounds.append(j)
    bounds.reverse()

    stages = []
    for i, dev in enumerate(devices):
        a, b = bounds[i], bounds[i + 1]
        comp, comm, params, _ = _stage_costs(profile, devices, cfg, i, a, b,
                                             D, batch, ctx)
        stages.append(StageAssignment(
            device_id=dev.device_id, address=dev.address,
            layer_start=a, layer_end=b, est_compute_sec=comp,
            est_comm_sec=comm, est_param_bytes=params,
            mesh_axes=_mesh_axes_for(dev)))
    return PartitionPlan(model=model_name, num_layers=L, stages=stages,
                         est_bottleneck_sec=best[D][L],
                         plan_version=plan_version)


def round_robin_plan(cfg: ModelConfig, model_name: str,
                     devices: Sequence[DeviceProfile],
                     plan_version: int = 0) -> PartitionPlan:
    """Even split ignoring device speed — the arrangement the reference
    actually shipped (``round_robin_module_arrangement``,
    ``server.py:893-905``)."""
    L, D = cfg.num_layers, len(devices)
    if D < 1 or D > L:
        raise PlanError(f"cannot split {L} layers over {D} devices")
    base, extra = divmod(L, D)
    stages, start = [], 0
    for i, dev in enumerate(devices):
        n = base + (1 if i < extra else 0)
        stages.append(StageAssignment(
            device_id=dev.device_id, address=dev.address,
            layer_start=start, layer_end=start + n,
            est_compute_sec=0.0, est_comm_sec=0.0, est_param_bytes=0,
            mesh_axes=_mesh_axes_for(dev)))
        start += n
    return PartitionPlan(model=model_name, num_layers=L, stages=stages,
                         est_bottleneck_sec=0.0, plan_version=plan_version)


# -- workload sketch input (telemetry/profiling.py, docs/DESIGN.md §20) ------

#: pinned with ``telemetry.profiling.SKETCH_SCHEMA_VERSION`` by
#: ``tools/check_sketch_schema.py`` — bump BOTH together.  Deliberately a
#: LITERAL copy, not an import: the planner parses committed sketch
#: artifacts without loading the serving stack.
SKETCH_SCHEMA_VERSION = 1

#: top-level keys every consumable artifact carries (same lint pins the
#: recorder's copy; ``load_workload_sketch`` enforces presence).
SKETCH_REQUIRED_KEYS = ("schema_version", "window_s", "requests",
                        "tenants", "prompt_tokens", "decode_tokens",
                        "interarrival_s", "prefix_hit")


class SketchError(ValueError):
    """A workload-sketch artifact the planner refuses to consume."""


def _hist_percentile(frag: dict, p: float) -> float:
    """Planner-side mirror of the recorder's fixed-edge histogram read:
    the upper edge of the bucket holding the p-quantile (conservative);
    the overflow bin reports the max seen."""
    edges = frag.get("edges") or []
    counts = [int(c) for c in (frag.get("counts") or [])]
    total = sum(counts)
    if not total:
        return 0.0
    target = p * total
    seen = 0
    for i, c in enumerate(counts):
        seen += c
        if seen >= target:
            return (float(edges[i]) if i < len(edges)
                    else float(frag.get("max", 0.0)))
    return float(frag.get("max", 0.0))


@dataclass(frozen=True)
class WorkloadSketch:
    """Planner view of one measured workload (the §20 sketch artifact):
    exactly the knobs ROADMAP item 3 names — ctx length, arrival rate,
    prefix share — distilled from the recorder's histograms."""

    requests: int
    window_s: float
    arrival_rate: float            # requests/sec over the window (0 = n/a)
    prompt_p50: float
    prompt_p95: float
    decode_p50: float
    decode_p95: float
    prefix_share: float
    tenants: Dict[str, int] = field(default_factory=dict)

    @property
    def ctx_tokens(self) -> int:
        """Context budget a plan should assume: p95 prompt + p95 decode
        (conservative bucket-edge reads, so a plan sized from this never
        under-reserves KV for the sketched traffic)."""
        return int(self.prompt_p95 + self.decode_p95)


def load_workload_sketch(src) -> WorkloadSketch:
    """Parse a sketch artifact into the planner's workload input.

    ``src``: a dict (already-parsed artifact), a JSON string, or a path
    to a JSON file (``tools/sketch.py`` writes both forms).  Raises
    :class:`SketchError` on a schema-version mismatch or missing keys —
    a mis-sized plan must fail loudly at planning time."""
    obj = src
    if isinstance(obj, str):
        if obj.lstrip().startswith("{"):
            obj = json.loads(obj)
        else:
            with open(obj) as f:
                obj = json.load(f)
    if not isinstance(obj, dict):
        raise SketchError(f"sketch artifact must be a JSON object, "
                          f"got {type(obj).__name__}")
    if obj.get("schema_version") != SKETCH_SCHEMA_VERSION:
        raise SketchError(
            f"sketch schema_version {obj.get('schema_version')!r} != "
            f"planner's pinned {SKETCH_SCHEMA_VERSION} — regenerate the "
            "artifact (or update BOTH pinned versions together)")
    missing = [k for k in SKETCH_REQUIRED_KEYS if k not in obj]
    if missing:
        raise SketchError(f"sketch artifact missing keys: {missing}")
    window = float(obj["window_s"])
    requests = int(obj["requests"])
    prefix = obj["prefix_hit"] or {}
    return WorkloadSketch(
        requests=requests,
        window_s=window,
        arrival_rate=(requests / window if window > 0 else 0.0),
        prompt_p50=_hist_percentile(obj["prompt_tokens"], 0.50),
        prompt_p95=_hist_percentile(obj["prompt_tokens"], 0.95),
        decode_p50=_hist_percentile(obj["decode_tokens"], 0.50),
        decode_p95=_hist_percentile(obj["decode_tokens"], 0.95),
        prefix_share=float(prefix.get("share", 0.0)),
        tenants={str(k): int(v)
                 for k, v in (obj.get("tenants") or {}).items()})


def plan_from_sketch(cfg: ModelConfig, model_name: str,
                     devices: Sequence[DeviceProfile], sketch,
                     batch: int = 1,
                     profile: Optional[ModelCostProfile] = None,
                     plan_version: int = 0) -> PartitionPlan:
    """:func:`plan_partition` driven by a measured workload sketch
    instead of a hand-picked ctx: the context budget is the sketch's
    p95 prompt + p95 decode (clamped to the model's window), discounted
    by the measured prefix-hit share — shared prefixes don't re-prefill,
    so the KV feasibility constraint should not charge them twice."""
    if not isinstance(sketch, WorkloadSketch):
        sketch = load_workload_sketch(sketch)
    ctx = sketch.ctx_tokens or min(cfg.max_seq_len, 1024)
    # prefix-shared tokens are resident once per tree, not once per
    # request: discount the per-request ctx the memory constraint sees
    ctx = int(ctx - sketch.prompt_p95 * min(1.0, max(0.0,
                                                     sketch.prefix_share)))
    ctx = max(1, min(cfg.max_seq_len, ctx))
    return plan_partition(cfg, model_name, devices, batch=batch, ctx=ctx,
                          profile=profile, plan_version=plan_version)


# -- plan caching (reference ip_module.json/session.json, server.py:805-820)

def save_plan_cache(path: str, plan: PartitionPlan) -> None:
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(plan.to_json(), f, indent=2)
    os.replace(tmp, path)


def load_cached_plan(path: str, model: str,
                     device_ids: Sequence[str]) -> Optional[PartitionPlan]:
    """Reload a cached plan when it still matches the model AND the exact
    device set (the reference reloads blindly; a changed fleet must replan)."""
    if not os.path.exists(path):
        return None
    try:
        with open(path) as f:
            plan = PartitionPlan.from_json(json.load(f))
    except (ValueError, KeyError, IndexError, TypeError):
        return None  # corrupt/stale cache: fall back to replanning
    if plan.model != model or plan.device_ids != list(device_ids):
        return None
    return plan
