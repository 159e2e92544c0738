"""Shared benchmark utilities: timing + CSV convention.

Every benchmark module exposes ``run() -> list[Row]``; benchmarks/run.py
prints one ``name,us_per_call,derived`` CSV line per row (the scaffold
contract): ``us_per_call`` measures the benchmark's own compute call and
``derived`` carries the headline metric being reproduced.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, List, Optional


@dataclasses.dataclass
class Row:
    name: str
    us_per_call: float
    derived: Any

    def csv(self) -> str:
        return f"{self.name},{self.us_per_call:.1f},{self.derived}"


def device_record() -> dict:
    """The devices a measurement ran on, as JAX reports them.  On a CPU
    platform the Pallas kernels ran in interpret mode."""
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def timed(fn: Callable, *args, repeats: int = 1, **kw):
    t0 = time.perf_counter()
    out = None
    for _ in range(repeats):
        out = fn(*args, **kw)
    dt = (time.perf_counter() - t0) / repeats
    return out, dt * 1e6
