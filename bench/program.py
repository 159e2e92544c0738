"""The system under test, reached through its public entry points.

This is the one module of the benchmark that imports the program
(``repro``): it turns a configuration's node list into the lowering IR's
``OpGraph``, its operating point into ``core.hw.OperatingPoint``, and
serves through ``exec.serving.ServingEngine``.
"""
from __future__ import annotations

import dataclasses
import math

from repro.core import hw
from repro.core.photonic_gemm import detection_sigma
from repro.core.types import Dataflow
from repro.exec import executor
from repro.exec.jax_cache import use_persistent_cache  # noqa: F401
from repro.exec.serving import MicroBatcher, ServingEngine  # noqa: F401
from repro.models import lowering as lw

_NODE_FIELDS = {f.name for f in dataclasses.fields(lw.OpNode)}


def graph(config: dict) -> lw.OpGraph:
    nodes = []
    for n in config["nodes"]:
        unknown = set(n) - _NODE_FIELDS
        if unknown:
            raise ValueError(f"{n['name']}: fields {sorted(unknown)} are "
                             f"not OpNode fields")
        fields = dict(n)
        fields["inputs"] = tuple(n.get("inputs", ()))
        nodes.append(lw.OpNode(**fields))
    return lw.OpGraph(tuple(nodes))


def operating_point(config: dict) -> hw.OperatingPoint:
    """The configuration's operating point, checked against the numbers
    the configuration states (N, bits, ADC bits, noise sigma)."""
    spec = config["operating_point"]
    if spec["constructor"] != "equal_area":
        raise ValueError(f"unknown constructor {spec['constructor']!r}")
    op = hw.OperatingPoint.equal_area(
        spec["backend"], Dataflow(spec["dataflow"]), spec["data_rate_gsps"],
        noise_enabled=spec["noise_enabled"])
    sigma = detection_sigma(op.kernel_config())
    stated = (spec["dpe_size"], spec["bits"], spec["adc_bits"])
    if (op.n, op.bits, op.adc_bits) != stated or not math.isclose(
            sigma, spec["noise_sigma_int"], rel_tol=1e-12, abs_tol=0.0):
        raise ValueError(
            f"{config['name']}: the program's operating point (N={op.n}, "
            f"bits={op.bits}, adc_bits={op.adc_bits}, sigma={sigma!r}) is "
            f"not the one the configuration states ({stated}, sigma="
            f"{spec['noise_sigma_int']!r})")
    return op


def engine(config: dict, params: dict, max_batch: int) -> ServingEngine:
    return ServingEngine(params, operating_point(config),
                         lowering=graph(config),
                         in_hw=tuple(config["input_hw"]),
                         max_batch=max_batch)


def trace_count() -> int:
    return executor.trace_count()


def simulated(eng: ServingEngine, bucket: int) -> dict:
    """The photonic model's own figures for one bucket's plan (simulated
    HEANA/AMW hardware, not the TPU)."""
    te = hw.trace_energy(eng.plans[bucket])
    return {"simulated_fps": te.fps,
            "simulated_j_per_image": te.j_per_image,
            "simulated_latency_s": te.latency_s,
            "bucket": bucket}
