"""Per-layer dataflow auto-scheduler (the paper's "flexible dataflows").

HEANA's TAOM + BPCA combination lets *each layer* of a CNN run under OS,
IS, or WS instead of the single fixed dataflow of prior MRR accelerators
(paper §4, §6.3).  This module exploits that: given a CNN as a list of
im2col GEMMs (models.cnn.LayerGemm) and an AcceleratorConfig, it searches
per layer over {OS, IS, WS} x kernel tiling with the event-driven cost
model (core.perf_model.best_dataflow) and emits a LayerPlan per layer plus
whole-CNN totals.

Because every layer independently takes the argmin of the same cost model
a fixed dataflow would be charged with, the planned CNN latency is <= the
latency under ANY single fixed dataflow — the auto-schedule can only tie
or beat the best fixed choice (benchmarks/autoflow.py asserts this across
the whole CNN zoo at batch 1 and 256).

Tiling: dataflow choice is an analytic-model decision; the tiling choice
is an *executor* decision — which (block_m, block_d) output tile the
Pallas kernel should use for this layer's GEMM.  The search minimizes
padded-output waste, then grid steps; numerics are tile-invariant, so this
is purely a performance knob.

Plans are cached content-addressed (exec.plan_cache): repeated shapes and
configs — within one CNN, across CNNs, or across processes via
dump()/load() — skip the search entirely.

Hashability: TileChoice, LayerPlan and CnnPlan are hashable by value so
they can serve as *static* arguments to jax.jit — the executor's compiled
forward (exec.executor.forward_fn) bakes the plan's tilings into the
traced program, and jit's own cache keys on the plan.  LayerPlan freezes
its ``candidates`` mapping at construction; CnnPlan hashes on what
determines it (layers, accelerator, batch, objective) and excludes the
derived perf-model ``result``.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Iterable, List, Optional, Sequence, Tuple, Union

from repro.core import dataflow as df
from repro.core import hw
from repro.core import perf_model as pm
from repro.core.types import Dataflow
from repro.exec import plan_cache as pc
# The kernel's own tile constraints and rounding — imported, not copied,
# so choose_tile cannot drift from what taom_gemm_quantized actually runs.
from repro.kernels.taom_gemm import LANE as _LANE
from repro.kernels.taom_gemm import SUBLANE as _SUBLANE
from repro.kernels.taom_gemm import VMEM_BUDGET_BYTES as _VMEM_BUDGET
from repro.kernels.taom_gemm import _round_up, vmem_bytes
from repro.kernels import taom_depthwise as _dw
from repro.models import lowering as lw
from repro.models.cnn import LayerGemm

# Large-M tiles cut grid steps: a batch-256 conv (M = 65536 rows) at
# block_m=256 pays 256 grid steps where block_m=4096 pays 16.  That
# ordering was tuned on interpret-mode wall-clock, not chip kernel times.
# Padding waste still dominates the choice, so small layers keep small
# tiles.  Large tiles do NOT all fit the chip: the kernel double-buffers
# (block_m, K-slot) and (block_m, block_d) f32 blocks, and (4096, 256)
# needs about 30 MiB of VMEM on v5e.  choose_tile therefore admits only
# tiles whose kernels.taom_gemm.vmem_bytes fits VMEM_BUDGET_BYTES.
_BLOCK_M_CANDIDATES = (8, 16, 32, 64, 128, 256, 512, 1024, 2048, 4096)
_BLOCK_D_CANDIDATES = (128, 256)
# v3: depthwise (count>1, d=1) layers choose their tile for the GEMM the
# executor actually runs — the fused block-diagonal (M, kk*kk*C) @ (.., C)
# — instead of the analytic per-group (M, kk*kk) @ (.., 1) shape.
# v4: plans embed the hardware operating point (repro.core.hw.
# OperatingPoint): scheduling may accept an OperatingPoint directly, the
# CnnPlan carries it for the executor's kernel-cfg coherence check, and
# persisted entries are stamped with the format version so pre-v4 dumps
# cleanly invalidate on load (plan_cache.PLAN_FORMAT_VERSION).
# v5: depthwise layers of analog-carry policies run grouped
# (kernels.taom_depthwise) and plan that kernel's tile.  Only those
# layers key on v5, so every other layer keeps its v4 key and its plan.
_PLAN_VERSION = 4
_DEPTHWISE_PLAN_VERSION = 5

#: What the scheduling entry points accept as "the hardware": a bare
#: AcceleratorConfig (legacy) or a full OperatingPoint (preferred — the
#: plan then pins the kernel config too).
HardwareSpec = Union[pm.AcceleratorConfig, hw.OperatingPoint]


def _resolve_hw(spec: HardwareSpec
                ) -> Tuple[pm.AcceleratorConfig, Optional[hw.OperatingPoint]]:
    if isinstance(spec, hw.OperatingPoint):
        return spec.accelerator_config(), spec
    return spec, None


class FrozenCandidates(dict):
    """Immutable, hashable dataflow -> modeled-latency mapping.

    A dict subclass so it stays JSON-serializable and keeps the plain
    ``plan.candidates["is"]`` read API, but with mutation blocked and a
    content hash — which is what lets LayerPlan (and through it CnnPlan)
    be a static jax.jit argument.
    """

    def __hash__(self) -> int:                       # type: ignore[override]
        return hash(tuple(sorted(self.items())))

    def _immutable(self, *args, **kw):
        raise TypeError("FrozenCandidates is immutable")

    __setitem__ = __delitem__ = _immutable
    clear = pop = popitem = setdefault = update = _immutable

    def __reduce__(self):
        # deepcopy/pickle rebuild through __init__ (C-level dict fill),
        # not item assignment, which is blocked.
        return (FrozenCandidates, (dict(self),))


@dataclasses.dataclass(frozen=True)
class TileChoice:
    """Kernel output-tile selection for one GEMM (executor knob)."""
    block_m: int
    block_d: int
    grid_m: int
    grid_d: int
    n_chunks: int          # temporal folds = ceil(K / DPE size)
    pad_waste: float       # padded-output overhead fraction (>= 0)


@dataclasses.dataclass(frozen=True)
class LayerPlan:
    """One layer's scheduled execution: dataflow + tiling + modeled cost."""
    name: str
    c: int                 # GEMM rows (batch already folded in)
    k: int
    d: int
    count: int             # parallel instances (depthwise groups)
    dataflow: Dataflow
    latency_s: float       # modeled, count included
    energy_j: float        # modeled (dynamic, no static share), count incl.
    candidates: Dict[str, float]   # dataflow value -> modeled latency (one
                                   # instance) for report/debugging
    tile: TileChoice
    cache_key: str
    # Run bookkeeping, not plan content: a plan served from the cache must
    # compare (and jit-cache) equal to the freshly searched one.
    cache_hit: bool = dataclasses.field(compare=False)

    def __post_init__(self):
        # Freeze the candidates mapping so the (frozen) dataclass hash —
        # required for static-jit use — is well defined.
        object.__setattr__(self, "candidates",
                           FrozenCandidates(self.candidates))

    @property
    def gemm(self) -> df.GemmShape:
        return df.GemmShape(self.c, self.k, self.d)


@dataclasses.dataclass(frozen=True, eq=False)
class CnnPlan:
    """A whole CNN's auto-scheduled execution plan.

    Hash/equality cover what *determines* the plan (layers, accelerator,
    batch, objective) — ``result`` is derived from those through the perf
    model and ``cache_hits``/``cache_misses`` are run bookkeeping, so two
    plans of the same problem compare equal (and hit the same jit trace)
    whether they came from the search or the plan cache.
    """
    layers: Tuple[LayerPlan, ...]
    acc: pm.AcceleratorConfig
    batch: int
    objective: str
    result: pm.InferenceResult     # perf-model totals under the plan
    cache_hits: int
    cache_misses: int
    # v4: the operating point the hardware was derived from, when the
    # plan was scheduled from one — lets the executor pin the kernel
    # config (bits/optics included) against the plan, and energy reports
    # carry full provenance.  None for legacy bare-AcceleratorConfig
    # plans (geometry-only coherence).
    op: Optional[hw.OperatingPoint] = None

    def _identity(self) -> tuple:
        return (self.layers, self.acc, self.batch, self.objective, self.op)

    def __eq__(self, other) -> bool:
        if not isinstance(other, CnnPlan):
            return NotImplemented
        return self._identity() == other._identity()

    def __hash__(self) -> int:
        return hash(self._identity())

    @property
    def dataflows(self) -> Tuple[Dataflow, ...]:
        return tuple(p.dataflow for p in self.layers)

    @property
    def latency_s(self) -> float:
        return self.result.latency_s

    @property
    def fps(self) -> float:
        return self.result.fps

    @property
    def fps_per_watt(self) -> float:
        return self.result.fps_per_watt

    def mix(self) -> Dict[str, int]:
        """How many layers landed on each dataflow."""
        out = {f.value: 0 for f in Dataflow}
        for p in self.layers:
            out[p.dataflow.value] += 1
        return out


def choose_tile(m: int, d: int, k: int, dpe_size: int) -> TileChoice:
    """Pick the kernel (block_m, block_d) for an (M, D) output.

    Only tiles whose kernel footprint (kernels.taom_gemm.vmem_bytes)
    fits the kernel's VMEM budget are admitted.  Among those, minimize
    padded-output elements first (don't burn MXU cycles on padding), then
    grid steps (fewer, larger tiles win ties).  Mirrors the kernel's own
    clamping so grid numbers here are exactly what it runs.
    """
    best = None
    for bm in _BLOCK_M_CANDIDATES:
        bm_eff = min(bm, _round_up(m, _SUBLANE))
        for bd in _BLOCK_D_CANDIDATES:
            bd_eff = min(bd, _round_up(d, _LANE))
            if vmem_bytes(bm_eff, bd_eff, dpe_size) > _VMEM_BUDGET:
                continue
            mp, dp = _round_up(m, bm_eff), _round_up(d, bd_eff)
            grid_m, grid_d = mp // bm_eff, dp // bd_eff
            score = (mp * dp, grid_m * grid_d, bm_eff, bd_eff)
            if best is None or score < best[0]:
                waste = mp * dp / float(m * d) - 1.0
                best = (score, TileChoice(bm_eff, bd_eff, grid_m, grid_d,
                                          max(1, -(-k // dpe_size)), waste))
    if best is None:
        raise ValueError(f"no kernel tile for dpe_size={dpe_size} fits the "
                         f"{_VMEM_BUDGET} B VMEM budget")
    return best[1]


def choose_depthwise_tile(m: int, c: int, k: int,
                          dpe_size: int) -> TileChoice:
    """The grouped depthwise kernel's tile for M output pixels of C
    channels: ``block_d`` the channel block with the least lane padding
    (kernels.taom_depthwise.choose_block_c; the kernel narrows it to one
    lane block at a stride above 1), ``block_m`` the pixels a grid step
    may hold, which the kernel fits to its VMEM budget in whole rows or
    images (``taom_depthwise.step_shape``: only the kernel sees the
    layer's width and stride).  ``n_chunks`` is the temporal
    folds of the layer's block-diagonal K = k*C, whose count sets the
    noise scale."""
    bd = _dw.choose_block_c(c)
    bm = min(_dw.BLOCK_M_DEFAULT, _round_up(m, _SUBLANE))
    cp = _round_up(c, bd)
    return TileChoice(bm, bd, -(-m // bm), cp // bd,
                      max(1, -(-(k * c) // dpe_size)), cp / float(c) - 1.0)


def _cache_payload(g: df.GemmShape, count: int, acc: pm.AcceleratorConfig,
                   objective: str, flows: Sequence[Dataflow],
                   grouped: bool = False) -> dict:
    payload = {
        "v": _PLAN_VERSION,
        "gemm": [g.c, g.k, g.d],
        "count": count,
        "acc": [acc.backend, acc.data_rate_gsps, acc.n, acc.m, acc.n_dpus],
        "objective": objective,
        "flows": sorted(f.value for f in flows),
        "tiles": [_BLOCK_M_CANDIDATES, _BLOCK_D_CANDIDATES],
        "vmem_budget": _VMEM_BUDGET,
    }
    if grouped:
        payload.update(v=_DEPTHWISE_PLAN_VERSION, depthwise="grouped",
                       tiles=[_dw.BLOCK_M_DEFAULT, _dw.BLOCK_C_CANDIDATES])
    return payload


def _plan_to_dict(p: LayerPlan) -> dict:
    d = dataclasses.asdict(p)
    d["dataflow"] = p.dataflow.value
    d.pop("name")          # content-addressed: names don't enter the cache
    d.pop("cache_hit")
    d["plan_version"] = pc.PLAN_FORMAT_VERSION  # load-time invalidation
    return d


def _plan_from_dict(d: dict, name: str, cache_hit: bool) -> LayerPlan:
    return LayerPlan(name=name, c=d["c"], k=d["k"], d=d["d"],
                     count=d["count"], dataflow=Dataflow(d["dataflow"]),
                     latency_s=d["latency_s"], energy_j=d["energy_j"],
                     candidates=dict(d["candidates"]),
                     tile=TileChoice(**d["tile"]),
                     cache_key=d["cache_key"], cache_hit=cache_hit)


def plan_layer(layer: LayerGemm, acc: HardwareSpec, batch: int = 1,
               objective: str = "latency",
               flows: Sequence[Dataflow] = tuple(Dataflow),
               cache: Optional[pc.PlanCache] = None) -> LayerPlan:
    """Schedule one layer: search dataflows x tiling, cache the result."""
    acc, _ = _resolve_hw(acc)
    cache = cache if cache is not None else pc.GLOBAL_PLAN_CACHE
    g = df.GemmShape(layer.c * batch, layer.k, layer.d)
    grouped = layer.depthwise and lw.grouped_depthwise(acc.backend)
    key = pc.fingerprint(_cache_payload(g, layer.count, acc, objective,
                                        flows, grouped))
    cached = cache.get(key)
    if cached is not None:
        return _plan_from_dict(cached, layer.name, cache_hit=True)

    flow, cost, costs = pm.best_dataflow(g, acc, flows, objective)
    # Dataflow cost is charged on the paper's analytic shape (count
    # grouped instances), but the tile must fit what the executor
    # actually runs — LayerGemm.executed owns that convention (grouped
    # depthwise taps, or one block-diagonal GEMM).
    em, ek, ed = LayerGemm(layer.name, g.c, g.k, g.d,
                           layer.count).executed(acc.backend)
    tile = (choose_depthwise_tile(em, ed, ek, acc.n) if grouped
            else choose_tile(em, ed, ek, acc.n))
    plan = LayerPlan(
        name=layer.name, c=g.c, k=g.k, d=g.d, count=layer.count,
        dataflow=flow,
        latency_s=cost.latency_s * layer.count,
        energy_j=cost.energy.total * layer.count,
        candidates={f.value: c.latency_s for f, c in costs.items()},
        tile=tile, cache_key=key, cache_hit=False)
    cache.put(key, _plan_to_dict(plan))
    return plan


def schedule_cnn(layers: Iterable[LayerGemm], acc: HardwareSpec,
                 batch: int = 1, objective: str = "latency",
                 flows: Sequence[Dataflow] = tuple(Dataflow),
                 cache: Optional[pc.PlanCache] = None) -> CnnPlan:
    """Auto-schedule a whole CNN: per-layer dataflow + tiling plan.

    ``acc`` is either a bare AcceleratorConfig (legacy) or an
    OperatingPoint (preferred): an OperatingPoint is resolved to its
    ``accelerator_config()`` for the search AND embedded in the returned
    plan, so the executor can verify the kernel config against the
    hardware the plan was actually scheduled for (plan v4).

    The returned plan's ``result`` holds the perf-model totals (FPS,
    FPS/W, latency, energy incl. static) under the mixed dataflows —
    computed by the same core.perf_model.cnn_inference everything else in
    the repo uses, so planned numbers are directly comparable to the
    fixed-dataflow figures of Figs. 11-14.
    """
    acc, op = _resolve_hw(acc)
    cache = cache if cache is not None else pc.GLOBAL_PLAN_CACHE
    layers = list(layers)
    plans: List[LayerPlan] = [
        plan_layer(layer, acc, batch, objective, flows, cache)
        for layer in layers]
    # Plan totals at the operating point's optics (default optics for
    # legacy plans) — the per-layer search itself stays at default
    # optics (dataflow_costs: the plan cache keys on the accelerator
    # config alone), so LayerPlan.energy_j is a default-optics figure;
    # ``result`` and hw.trace_energy are the op-coherent totals.
    result = pm.cnn_inference(layers, acc, batch,
                              dataflows=[p.dataflow for p in plans],
                              optics=op.optics if op is not None else None)
    hits = sum(1 for p in plans if p.cache_hit)
    return CnnPlan(layers=tuple(plans), acc=acc, batch=batch,
                   objective=objective, result=result,
                   cache_hits=hits, cache_misses=len(plans) - hits, op=op)


def schedule_buckets(layers: Iterable[LayerGemm], acc: HardwareSpec,
                     batches: Sequence[int], objective: str = "latency",
                     flows: Sequence[Dataflow] = tuple(Dataflow),
                     cache: Optional[pc.PlanCache] = None,
                     ) -> Dict[int, CnnPlan]:
    """Schedule one network at several batch sizes (the serving buckets).

    The batched serving engine (exec.serving) plans every power-of-two
    bucket ahead of time; this keeps all of a network's bucket plans on
    one shared plan cache, so layers whose batched GEMM shape repeats
    across buckets (the fc layer, depthwise groups) hit instead of
    re-searching.  Returns {batch: CnnPlan} in the given bucket order.
    """
    cache = cache if cache is not None else pc.GLOBAL_PLAN_CACHE
    layers = list(layers)
    return {int(b): schedule_cnn(layers, acc, batch=int(b),
                                 objective=objective, flows=flows,
                                 cache=cache)
            for b in batches}
