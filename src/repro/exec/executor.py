"""End-to-end CNN executor over the Pallas TAOM kernel.

Runs a *runnable* GEMM-lowered CNN (a models.lowering.OpGraph — stride/
padding convs, depthwise convs, pooling, residuals, concats, shuffles —
or a legacy flat models.cnn.LoweredLayer tuple, + params dict)
image-batch in, logits out, with every GEMM executed by
kernels.ops.photonic_matmul: quantize -> TAOM kernel (Pallas; interpreted
on CPU) -> rescale.  This turns the repo's analytic per-figure scripts
into an actual inference engine producing real activations — the
reduced-scale variants of the paper's four evaluation CNNs
(models.zoo_cnn.ZOO) run through here.

Batching follows the paper's Toeplitz accounting: the image batch folds
into the GEMM M axis (all images' im2col rows concatenated), which is both
the batch-serving shape and what core.perf_model charges for batched
layers.  Detection-noise keys are threaded per layer — fold_in(key,
layer_index) — so every layer draws independent noise and runs are
reproducible from one root key.

The executor consumes a CnnPlan from exec.scheduler: each layer's GEMM
uses the plan's kernel tiling (block_m, block_d).  The plan's *dataflow*
choice changes scheduling (latency/energy in the report), never numerics —
with noise disabled the executed network equals the pure-jnp reference
(kernels/ref.py) bit-exactly, whatever the plan says (tests pin this).

Hot path (the serving contract HEANA's buffer-less pitch implies — the
loop must never stall on the host):

  * ``forward_fn`` is a pure jax.jit function of (params, x, key) with the
    lowering, plan (tilings), cfg and impl baked in as *static* arguments;
    one warm call = one cached executable, zero retracing, zero host syncs;
  * per-layer numerics fingerprints (mean |activation|) are computed
    on-device inside the compiled program and returned as ONE stacked
    array; ``ExecutionResult.traces`` materializes them lazily, only when
    a caller actually asks — never as per-layer ``float()`` syncs in the
    loop;
  * ``compiled_forward`` memoizes the jit wrapper under (lowering
    fingerprint, plan cache keys, cfg, impl); jax.jit's own cache then
    keys the executable on the batch shape/dtype — repeated serving calls
    hit a traced executable;
  * ``execute_cnn`` stays the thin eager-looking wrapper with today's
    ExecutionResult API (``compiled=False`` opts back into the eager
    op-by-op path, kept for the throughput benchmark's baseline).
"""
from __future__ import annotations

import dataclasses
import functools
import threading
from collections import OrderedDict
from typing import Callable, Dict, List, Optional, Sequence, Union

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from repro.core import dataflow as df
from repro.core import hw
from repro.core.types import PhotonicConfig
from repro.exec import plan_cache as pc
from repro.exec.scheduler import CnnPlan, LayerPlan
from repro.kernels import ops
from repro.models import cnn as cnn_mod
from repro.models import lowering as lw

_LOWERING_FP_VERSION = 2

#: A runnable network description: op-graph IR or legacy flat tuple.
Lowering = Union[lw.OpGraph, Sequence[cnn_mod.LoweredLayer]]


@dataclasses.dataclass
class LayerTrace:
    """What actually ran for one layer (executed next to modeled)."""
    name: str
    m: int                 # executed GEMM rows (batch folded in)
    k: int
    d: int
    dataflow: str
    block_m: int
    block_d: int
    latency_s: float       # modeled (from the plan)
    energy_j: float        # modeled (from the plan)
    out_mean_abs: float    # executed-numerics fingerprint
    # Executed-trace energy accounting (PR 5): the temporal folds the
    # kernel actually ran (the tile's K chunking), the hardware ADC
    # conversions the executed schedule implies, and the per-layer energy
    # charged from those executed counts via core.energy — one
    # core.perf_model.gemm_cost accounting path for modeled AND executed.
    n_chunks: int = 0
    adc_conversions: int = 0
    executed_energy_j: float = 0.0


@dataclasses.dataclass
class ExecutionResult:
    """Logits + plan + lazily materialized per-layer traces.

    ``fingerprints`` is the (n_layers,) device array of mean-|activation|
    per layer, computed inside the compiled forward.  ``traces`` converts
    it to floats on FIRST ACCESS — a serving loop that never reads traces
    never syncs on them.
    """
    logits: jnp.ndarray
    plan: CnnPlan
    fingerprints: jnp.ndarray
    activations: Optional[List[jnp.ndarray]] = None
    _traces: Optional[List[LayerTrace]] = dataclasses.field(
        default=None, repr=False)
    _energy: Optional[hw.TraceEnergy] = dataclasses.field(
        default=None, repr=False)

    @property
    def traces(self) -> List[LayerTrace]:
        if self._traces is None:
            fp = [float(v) for v in jax.device_get(self.fingerprints)]
            energy = self.energy()
            acc = self.plan.acc
            self._traces = []
            for i, p in enumerate(self.plan.layers):
                # "what actually ran": depthwise layers execute as ONE
                # fused block-diagonal GEMM, so trace the executed
                # (M, K, D) — LayerGemm.executed owns the convention —
                # consistent with the tile the scheduler sized for it.
                m, k, d = lw.LayerGemm(p.name, p.c, p.k, p.d,
                                       p.count).executed
                # Hardware event counts behind the executed energy: ADCs
                # are charged on the paper's grouped accounting (the
                # fused depthwise GEMM is a host-simulation device, its
                # structural zeros are not photonic work).
                sch = df.schedule(df.GemmShape(p.c, p.k, p.d), p.dataflow,
                                  acc.n, acc.m, acc.has_bpca)
                self._traces.append(LayerTrace(
                    name=p.name, m=m, k=k, d=d,
                    dataflow=p.dataflow.value, block_m=p.tile.block_m,
                    block_d=p.tile.block_d, latency_s=p.latency_s,
                    energy_j=p.energy_j, out_mean_abs=fp[i],
                    n_chunks=p.tile.n_chunks,
                    adc_conversions=sch.adc_conversions * p.count,
                    executed_energy_j=energy.per_layer_j[i]))
        return self._traces

    @property
    def modeled_latency_s(self) -> float:
        return self.plan.latency_s

    @property
    def modeled_fps(self) -> float:
        return self.plan.fps

    def energy(self) -> hw.TraceEnergy:
        """Executed-trace energy/FPS accounting of this run (memoized).

        Computed host-side from the plan's executed layer list via
        core.hw.trace_energy — NO device sync (unlike ``traces``, which
        materializes the numerics fingerprints): a serving loop can read
        joules without stalling the stream.
        """
        if self._energy is None:
            self._energy = hw.trace_energy(self.plan)
        return self._energy

    @property
    def executed_energy_j(self) -> float:
        """Total executed-trace energy for this batch (static incl.)."""
        return self.energy().energy_j

    @property
    def executed_fps_per_watt(self) -> float:
        return self.energy().fps_per_watt

    def block_until_ready(self) -> "ExecutionResult":
        """Wait for the device computation (for timing/benchmarks)."""
        self.logits.block_until_ready()
        return self


def _norm_lowering(lowering):
    """Default + normalize: None -> the small CNN; OpGraph passes
    through; anything else is frozen into a legacy flat tuple (both
    forms are hashable, as static jit arguments must be)."""
    if lowering is None:
        return cnn_mod.small_cnn_lowering()
    if isinstance(lowering, lw.OpGraph):
        return lowering
    return tuple(lowering)


def _layer_matmul(cols: jnp.ndarray, w: jnp.ndarray, cfg: PhotonicConfig,
                  key: Optional[jax.Array], plan: LayerPlan,
                  impl: str, mesh: Optional[Mesh]) -> jnp.ndarray:
    return ops.photonic_matmul(cols, w, cfg, key=key, impl=impl,
                               block_m=plan.tile.block_m,
                               block_d=plan.tile.block_d, mesh=mesh)


# ---------------------------------------------------------------------------
# Pure forward (the jit-compiled hot path)
# ---------------------------------------------------------------------------
# Counts Python executions of the forward body.  Under jit the body runs
# only while TRACING, so a warm compiled call leaves the counter untouched
# — tests and benchmarks/throughput.py assert no-retrace with this.
# Guarded by a lock: concurrent serving threads may trace simultaneously
# (cold buckets), and ``count += 1`` is not atomic across the read/write —
# a lost increment would let a real retrace slip past the no-retrace gates.
_TRACE_COUNT = 0
_TRACE_LOCK = threading.Lock()


def trace_count() -> int:
    """How many times the forward body has been traced/executed in Python."""
    with _TRACE_LOCK:
        return _TRACE_COUNT


def _forward(params: Dict[str, jnp.ndarray], x: jnp.ndarray,
             key: Optional[jax.Array] = None, *,
             lowering, plan: CnnPlan, cfg: PhotonicConfig, impl: str,
             collect_activations: bool, mesh: Optional[Mesh] = None):
    """Pure forward: (params, x, key) -> (logits, fingerprints, acts).

    Walks the lowering's op graph (models.lowering.graph_forward): every
    GEMM-bearing node (conv / depthwise_conv / fc) runs through the
    photonic kernel with its LayerPlan's tiling and an independent noise
    key; glue nodes (pool / residual_add / concat / shuffle / slice) are
    plain jnp ops.  Everything after the array arguments is static
    configuration; no host sync happens anywhere in the body
    (fingerprints stay device arrays).  Fingerprints are per GEMM node,
    taken right after its activation (before any downstream glue).
    ``mesh``: the batch of ``x`` is sharded over it (data-parallel
    serving); each GEMM kernel then runs per device on its rows.
    """
    global _TRACE_COUNT
    with _TRACE_LOCK:
        _TRACE_COUNT += 1
    graph = cnn_mod.as_graph(lowering, plan=plan)

    def mm(a2d: jnp.ndarray, w2d: jnp.ndarray, gi: int,
           node: lw.OpNode) -> jnp.ndarray:
        layer_key = (jax.random.fold_in(key, gi)
                     if key is not None and cfg.noise_enabled else None)
        return _layer_matmul(a2d, w2d, cfg, layer_key, plan.layers[gi],
                             impl, mesh)

    vals = lw.graph_forward(params, x, graph, mm)
    gemm_outs = [vals[n.name] for n in graph.gemm_nodes]
    # mean |activation| via explicit reciprocal multiply — jnp.mean's
    # division by the (constant) element count is reassociated by XLA
    # under jit but not eagerly, and the compiled-vs-eager contract
    # covers the fingerprints too.  Each is named by its node, like the
    # node's own operations.
    fingerprints = []
    for node, v in zip(graph.gemm_nodes, gemm_outs):
        with jax.named_scope(node.name):
            fingerprints.append(jnp.sum(jnp.abs(v)) * (1.0 / v.size))
    acts = tuple(gemm_outs) if collect_activations else ()
    return (vals[graph.output.name], jnp.stack(fingerprints), acts)


forward_fn = jax.jit(_forward, static_argnames=(
    "lowering", "plan", "cfg", "impl", "collect_activations", "mesh"))
"""jit entry point: ``forward_fn(params, x, key, lowering=..., plan=...,
cfg=..., impl=..., collect_activations=..., mesh=...)`` with the keyword
arguments static — CnnPlan/LayerPlan/TileChoice and PhotonicConfig are
hashable by value precisely so they can sit in jit's cache key."""


def lowering_fingerprint(lowering) -> str:
    """Content address of a lowered network structure (not its weights).

    Covers both forms: op graphs hash every node field; legacy flat
    tuples keep their historical layout (under a bumped version — the
    graph path changed what a lowering can express)."""
    if isinstance(lowering, lw.OpGraph):
        layers = [dataclasses.asdict(n) for n in lowering.nodes]
        for d in layers:
            d["inputs"] = list(d["inputs"])
    else:
        layers = [[l.name, l.kind, l.relu, l.pool_after, l.kk]
                  for l in lowering]
    return pc.fingerprint({"v": _LOWERING_FP_VERSION, "layers": layers})


# Executable-wrapper memo: (lowering fp, per-layer plan cache keys, cfg,
# impl, collect) -> partial over forward_fn.  jax.jit's own cache then
# adds the batch shape/dtype — together that is the compilation cache
# serving calls hit.  LRU-bounded for the same reason PlanCache is: a
# long-lived serving process streaming distinct plans must not grow
# without limit.  (Evicting a wrapper drops its pinned CnnPlan/lowering;
# traced executables already in jit's global cache are NOT reclaimed —
# call jax.clear_caches() if that ever matters.)
#
# All access goes through _FORWARD_LOCK: the serving front-end
# (exec.serving) calls compiled_forward from concurrent request threads,
# and an unguarded get/insert/move_to_end/popitem sequence on the
# OrderedDict can corrupt its internal linkage or evict mid-iteration.
_FORWARD_CACHE: "OrderedDict[tuple, Callable]" = OrderedDict()
_FORWARD_CACHE_MAX = 256
_FORWARD_LOCK = threading.RLock()


def compiled_forward(plan: CnnPlan, cfg: PhotonicConfig,
                     lowering: Optional[Lowering] = None,
                     impl: str = "auto",
                     collect_activations: bool = False,
                     mesh: Optional[Mesh] = None) -> Callable:
    """The compiled serving entry: returns ``fn(params, x, key=None)``.

    ``mesh``: ``x`` will arrive batch-sharded over it (see ``_forward``).

    Warm calls execute a cached jit executable — no retracing, no
    per-layer host syncs.  Two plans that solve the same planning problems
    (same content-addressed cache keys) share one wrapper even if they are
    distinct objects.  Thread-safe: concurrent serving threads may call
    this freely (they serialize only on the memo lookup, not the forward).
    """
    lowering = _norm_lowering(lowering)
    impl = "pallas" if impl == "auto" else impl
    memo_key = (lowering_fingerprint(lowering),
                tuple(p.cache_key for p in plan.layers), cfg, impl,
                collect_activations, mesh)
    with _FORWARD_LOCK:
        fn = _FORWARD_CACHE.get(memo_key)
        if fn is None:
            fn = functools.partial(forward_fn, lowering=lowering, plan=plan,
                                   cfg=cfg, impl=impl,
                                   collect_activations=collect_activations,
                                   mesh=mesh)
            _FORWARD_CACHE[memo_key] = fn
            while len(_FORWARD_CACHE) > _FORWARD_CACHE_MAX:
                _FORWARD_CACHE.popitem(last=False)
        else:
            _FORWARD_CACHE.move_to_end(memo_key)
        return fn


def compile_cache_stats() -> dict:
    with _FORWARD_LOCK:
        return {"entries": len(_FORWARD_CACHE),
                "max_entries": _FORWARD_CACHE_MAX}


def clear_compile_cache() -> None:
    with _FORWARD_LOCK:
        _FORWARD_CACHE.clear()
        _validate_geometry.cache_clear()


# ---------------------------------------------------------------------------
# Validation (eager, before tracing — clear errors instead of reshape noise)
# ---------------------------------------------------------------------------
def _gemm_count(lowering) -> int:
    if isinstance(lowering, lw.OpGraph):
        return len(lowering.gemm_nodes)
    return len(lowering)


def _validate(x: jnp.ndarray, plan: CnnPlan, cfg: PhotonicConfig,
              lowering, key: Optional[jax.Array]) -> None:
    if x.ndim != 4:
        raise ValueError(f"x must be (N, H, W, C) images, got shape "
                         f"{tuple(x.shape)}")
    if len(plan.layers) != _gemm_count(lowering):
        raise ValueError(
            f"plan has {len(plan.layers)} layers, lowering has "
            f"{_gemm_count(lowering)} GEMM layers — plan the "
            f"lowered_gemms of this network")
    n, h, w = x.shape[0], x.shape[1], x.shape[2]
    if n != plan.batch:
        raise ValueError(
            f"plan was scheduled for batch {plan.batch} but x has batch "
            f"{n} — modeled and executed numbers would disagree; for "
            f"mixed-size traffic use exec.serving.ServingEngine, which "
            f"pads each request up to a power-of-two batch bucket with "
            f"its own pre-traced plan and slices the results back")
    if cfg.noise_enabled and key is None:
        raise ValueError(
            "cfg.noise_enabled=True but key=None — pass a root PRNG key "
            "(per-layer keys are folded in) or set noise_enabled=False")
    # Kernel-cfg / plan hardware coherence: a PhotonicConfig whose DPE
    # geometry, backend or data rate disagrees with the hardware the plan
    # was scheduled for used to execute without complaint — the numerics
    # then silently diverged from the modeled latency/energy the result
    # reports.  Plans carrying an OperatingPoint (plan v4) additionally
    # pin bits and optics.
    hw.check_kernel_plan_coherence(cfg, plan)
    # lru_cache's C implementation is safe on CPython, but the contract
    # here ("warm loop pays the graph walk once") shouldn't depend on
    # that detail: serialize on the same lock the wrapper memo uses so
    # concurrent serving threads can't interleave memo fill + clear.
    with _FORWARD_LOCK:
        _validate_geometry(lowering, plan, h, w)


@functools.lru_cache(maxsize=_FORWARD_CACHE_MAX)
def _validate_geometry(lowering, plan: CnnPlan, h: int, w: int) -> None:
    """Structural checks, memoized: the outcome is fully determined by
    (lowering, plan, H, W) — all hashable — so a warm serving loop pays
    the Python graph walk once per distinct geometry, not per call.
    (lru_cache does not cache raises: invalid combinations re-raise
    their clear error every call.)  Bounded like _FORWARD_CACHE — each
    entry pins its plan/lowering — and cleared by clear_compile_cache.

    Infers every node's shape for THESE spatial dims — raising the IR's
    explicit errors for indivisible pooling / mismatched branches —
    then pins each GEMM node against its LayerPlan: the plan must have
    been built for exactly this input geometry.
    """
    graph = cnn_mod.as_graph(lowering, plan=plan)
    shapes = lw.infer_shapes(graph, (h, w))
    for node, lplan in zip(graph.gemm_nodes, plan.layers):
        oh, ow, oc = shapes[node.name]
        rows = plan.batch if node.op == "fc" else plan.batch * oh * ow
        if lplan.c != rows:
            where = (f"the batch is {plan.batch}" if node.op == "fc" else
                     f"the input reaches this layer as {plan.batch} x "
                     f"{oh}x{ow} = {rows} rows")
            raise ValueError(
                f"{node.name}: plan expects {lplan.c} GEMM rows but "
                f"{where} — plan_for_network(in_hw=({h}, {w})) "
                f"for this input size")
        if node.op == "depthwise_conv":
            ic = shapes[node.inputs[0]][2]
            if lplan.count != ic:
                raise ValueError(
                    f"{node.name}: plan has count={lplan.count} depthwise "
                    f"groups but the input reaches this layer with "
                    f"{ic} channels — replan this network")
        elif lplan.d != oc:
            raise ValueError(
                f"{node.name}: plan has D={lplan.d} output channels but "
                f"the lowering implies {oc} — plan and lowering come "
                f"from different networks")


# ---------------------------------------------------------------------------
# Public wrapper (today's ExecutionResult API)
# ---------------------------------------------------------------------------
def execute_cnn(params: Dict[str, jnp.ndarray], x: jnp.ndarray,
                plan: CnnPlan, cfg: PhotonicConfig,
                key: Optional[jax.Array] = None,
                impl: str = "auto",
                lowering: Optional[Lowering] = None,
                collect_activations: bool = False,
                compiled: bool = True) -> ExecutionResult:
    """Run a lowered CNN end-to-end through the photonic kernel.

    params: weight dict keyed by GEMM-node (or LoweredLayer) name.
    x: (N, H, W, C) image batch (H != W is fine; the plan must have been
      built for the same spatial dims, see plan_for_network(in_hw=...)).
    plan: CnnPlan over lowered_gemms(params, lowering) at batch >= 1 —
      layer order must match the lowering's GEMM nodes (schedule_cnn
      preserves it).
    key: root PRNG key for detection noise (per-layer keys are folded in);
      REQUIRED when cfg.noise_enabled, forbidden-to-matter otherwise.
    impl: 'pallas' | 'ref' | 'auto' (forwarded to ops.photonic_matmul).
    lowering: an op-graph (models.lowering.OpGraph — models.zoo_cnn holds
      the paper networks' runnable variants) or a legacy flat
      LoweredLayer tuple; defaults to the small CNN.
    compiled: route through the jit-compiled forward (default).  False
      runs the same body op-by-op in Python — the slow pre-fix behavior,
      kept as the measurable baseline for benchmarks/throughput.py.
    """
    lowering = _norm_lowering(lowering)
    impl = "pallas" if impl == "auto" else impl
    _validate(x, plan, cfg, lowering, key)
    if compiled:
        fn = compiled_forward(plan, cfg, lowering, impl,
                              collect_activations)
        logits, fingerprints, acts = fn(params, x, key)
    else:
        logits, fingerprints, acts = _forward(
            params, x, key, lowering=lowering, plan=plan, cfg=cfg,
            impl=impl, collect_activations=collect_activations)
    return ExecutionResult(
        logits=logits, plan=plan, fingerprints=fingerprints,
        activations=list(acts) if collect_activations else None)


def reference_forward(params: Dict[str, jnp.ndarray], x: jnp.ndarray,
                      cfg: PhotonicConfig,
                      lowering: Optional[Lowering] = None) -> jnp.ndarray:
    """Pure-jnp oracle forward: same quantize->accumulate->ADC math via
    kernels/ref.py, driven through the SAME lowered structure the executor
    runs (models.cnn.lowered_apply) — so the oracle covers any lowered
    network, not just the small CNN.

    The bit-exactness contract (noise disabled) is kernel == oracle under
    the same compilation: execute_cnn(..., impl='pallas') equals
    execute_cnn(..., impl='ref') exactly, one jitted program with only
    the GEMM swapped.  This eager forward is another program, whose glue
    XLA may round differently (1 ULP at googlenet_mini's global average
    on the CPU backend).  A noise-enabled cfg raises (the oracle is
    deterministic by definition; disable noise explicitly).
    """
    mm: Callable = lambda a, w: ops.photonic_matmul(a, w, cfg, impl="ref")
    return cnn_mod.lowered_apply(params, x, _norm_lowering(lowering),
                                 matmul=mm)


def plan_for_network(params: Dict[str, jnp.ndarray],
                     acc, batch: int = 1, in_hw=16,
                     lowering: Optional[Lowering] = None,
                     **schedule_kw) -> CnnPlan:
    """Convenience: lower a runnable network's GEMM table and schedule it.

    ``acc``: an AcceleratorConfig or (preferred) a core.hw.OperatingPoint
    — the latter is embedded in the plan so the executor can hold the
    kernel config coherent with it.
    ``in_hw``: input spatial size — an int for square images or an (H, W)
    pair for rectangular ones.
    """
    from repro.exec.scheduler import schedule_cnn
    gemms = cnn_mod.lowered_gemms(params, lowering, in_hw)
    return schedule_cnn(gemms, acc, batch=batch, **schedule_kw)
