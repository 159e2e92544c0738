"""A configuration's network, read from its JSON node list.

Pure Python: shape inference and the logical GEMM table that the work
model (``work.py``) and the tests use.  The node fields are those of the
program's lowering IR (``name``, ``op``, ``inputs``, ``cout``, ``kh``,
``kw``, ``stride``, ``padding``, ``relu``, ``pool``, ``pool_size``,
``pool_stride``); every field a node's op reads is written out in the
file, so nothing here depends on the program's defaults.
"""
from __future__ import annotations

import dataclasses
import json
import os
from typing import Dict, List, Tuple

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
REPO_DIR = os.path.dirname(BENCH_DIR)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_config(name: str) -> dict:
    return load_json(os.path.join(BENCH_DIR, "configs", f"{name}.json"))


def load_traffic(name: str) -> dict:
    return load_json(os.path.join(BENCH_DIR, "traffic", f"{name}.json"))


@dataclasses.dataclass(frozen=True)
class Gemm:
    """One layer as the paper counts it: ``count`` GEMMs of
    (M rows per image) x K @ K x D."""
    name: str
    m: int
    k: int
    d: int
    count: int = 1


def out_dim(size: int, k: int, stride: int, padding: str) -> int:
    if padding == "same":
        return -(-size // stride)
    return (size - k) // stride + 1


def infer_shapes(nodes: List[dict], in_hw) -> Dict[str, Tuple[int, int, int]]:
    """(H, W, C) of every node's output for one image."""
    h, w = in_hw
    shapes: Dict[str, Tuple[int, int, int]] = {}
    for n in nodes:
        op = n["op"]
        if op == "input":
            shapes[n["name"]] = (h, w, n["cout"])
            continue
        ih, iw, ic = shapes[n["inputs"][0]]
        if op in ("conv", "depthwise_conv"):
            oh = out_dim(ih, n["kh"], n["stride"], n["padding"])
            ow = out_dim(iw, n["kw"], n["stride"], n["padding"])
            shapes[n["name"]] = (oh, ow, ic if op == "depthwise_conv"
                                 else n["cout"])
        elif op == "fc":
            shapes[n["name"]] = (1, 1, n["cout"])
        elif op == "pool":
            if n["pool"] == "global":
                shapes[n["name"]] = (1, 1, ic)
            else:
                shapes[n["name"]] = (
                    out_dim(ih, n["pool_size"], n["pool_stride"],
                            n["padding"]),
                    out_dim(iw, n["pool_size"], n["pool_stride"],
                            n["padding"]), ic)
        elif op == "residual_add":
            if shapes[n["inputs"][1]] != (ih, iw, ic):
                raise ValueError(f"{n['name']}: residual inputs disagree")
            shapes[n["name"]] = (ih, iw, ic)
        else:
            raise ValueError(f"{n['name']}: op {op!r} has no shape rule "
                             f"here")
    return shapes


def logical_gemms(config: dict) -> List[Gemm]:
    """The paper's GEMM table of the network, per image, in node order:
    conv (OH*OW, kh*kw*C_in, C_out); depthwise C groups of
    (OH*OW, kh*kw, 1); fc (1, H*W*C, D)."""
    nodes = config["nodes"]
    shapes = infer_shapes(nodes, config["input_hw"])
    out: List[Gemm] = []
    for n in nodes:
        if n["op"] not in ("conv", "depthwise_conv", "fc"):
            continue
        ih, iw, ic = shapes[n["inputs"][0]]
        oh, ow, oc = shapes[n["name"]]
        if n["op"] == "conv":
            out.append(Gemm(n["name"], oh * ow, n["kh"] * n["kw"] * ic, oc))
        elif n["op"] == "depthwise_conv":
            out.append(Gemm(n["name"], oh * ow, n["kh"] * n["kw"], 1,
                            count=ic))
        else:
            out.append(Gemm(n["name"], 1, ih * iw * ic, oc))
    return out
