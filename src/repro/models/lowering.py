"""General CNN lowering IR: op graphs lowered to im2col GEMMs + glue.

HEANA consumes convolution networks as GEMMs via the Toeplitz/im2col
transform (paper §2.1); everything *between* the GEMMs — pooling,
residual adds, branch concats, channel shuffles — is cheap digital glue
handled by the accelerator tile's post-GEMM units (Fig. 10).  This
module is the single source of truth for that lowering, shared by:

  * the executor (repro.exec.executor), which replays a graph through
    the Pallas kernel with per-layer plans and noise keys;
  * the pure-jnp oracle (repro.exec.reference_forward), which replays
    the SAME graph through kernels/ref.py;
  * the analytic side (``graph_gemms``), which emits the per-layer
    LayerGemm table the scheduler and perf model consume — so planned
    shapes and executed shapes cannot drift.

The IR is a flat topologically-ordered tuple of ``OpNode``s (an
``OpGraph``).  Node kinds:

  ``input``           the graph input (carries C_in in ``cout``)
  ``conv``            kh x kw conv, stride/padding, -> im2col GEMM
                      with K = kh*kw*C_in, D = cout
  ``depthwise_conv``  per-channel kh x kw conv, accounted analytically
                      as ``count=C`` grouped (kh*kw, 1) GEMMs, matching
                      the paper's depthwise tables; executed as those
                      grouped taps under an analog-carry policy and as
                      ONE block-diagonal GEMM (K = kh*kw*C, D = C) under
                      a chunk-ADC one (``grouped_depthwise``)
  ``pool``            max / avg / global — glue, no GEMM
  ``residual_add``    elementwise sum of two same-shape producers
  ``concat``          channel concat of >= 2 producers
  ``shuffle``         ShuffleNet channel shuffle (``groups``)
  ``slice``           channel slice [c_lo, c_hi) (ShuffleNet split)
  ``fc``              flatten -> (K, D) GEMM

Graphs are frozen and hashable by value so they can sit directly in
jax.jit static arguments (the executor bakes the graph into the traced
program exactly like the plan's tilings).
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.photonic_gemm import ANALOG_CARRY_BACKENDS

GEMM_OPS = ("conv", "depthwise_conv", "fc")
GLUE_OPS = ("pool", "residual_add", "concat", "shuffle", "slice")
OPS = ("input",) + GEMM_OPS + GLUE_OPS
POOL_KINDS = ("max", "avg", "global")
PADDINGS = ("same", "valid")


# ---------------------------------------------------------------------------
# Analytic GEMM record (the scheduler/perf-model currency)
# ---------------------------------------------------------------------------
#: The most input channels a dense conv may have to take the small-C_in
#: route (``small_cin_conv``).
SMALL_CIN_MAX = 8


def small_cin_conv(node: "OpNode", cin: int) -> bool:
    """Does ``node`` build its GEMM operand from the quantized image
    (kernels.ops.photonic_conv_small_cin) instead of ``im2col``?  Dense
    convs whose input has at most ``SMALL_CIN_MAX`` channels do: with C
    on the lanes, each of im2col's kh*kw slices is a lane-sparse pass
    over the image."""
    return node.op == "conv" and cin <= SMALL_CIN_MAX


def grouped_depthwise(backend) -> bool:
    """Do ``backend``'s depthwise layers run as grouped kh*kw-tap reads
    (kernels.taom_depthwise)?  Under analog carry each output is one
    noise draw and one ADC read of an exact integer sum, so the chunk
    layout of K cannot change it.  Chunk-ADC policies (AMW, MAW) keep
    the block-diagonal GEMM: their per-chunk noise and reads depend on
    how K is laid out."""
    return backend in ANALOG_CARRY_BACKENDS


@dataclasses.dataclass(frozen=True)
class LayerGemm:
    """One layer as an im2col GEMM: I (C x K) @ W (K x D), ``count``
    parallel instances (depthwise groups)."""
    name: str
    c: int      # output pixels (rows of I)
    k: int      # C_in * kh * kw (contraction)
    d: int      # output channels
    count: int = 1   # parallel instances (e.g. depthwise groups)

    @property
    def macs(self) -> int:
        return self.c * self.k * self.d * self.count

    @property
    def depthwise(self) -> bool:
        """What graph_gemms emits for ``depthwise_conv`` nodes."""
        return self.count > 1 and self.d == 1

    def executed(self, backend) -> Tuple[int, int, int]:
        """The (M, K, D) the executor actually runs under ``backend``.

        This is the single home of the fusion convention.  Depthwise
        layers run grouped under an analog-carry policy
        (``grouped_depthwise``): (M, kh*kw, C), each of the C outputs
        reading its own kh*kw taps.  Under a chunk-ADC policy they run
        as one block-diagonal GEMM (depthwise_block_diag), so K and D
        scale by count.  Every other layer executes its analytic shape
        as-is.  The scheduler sizes kernel tiles and the executor
        reports traces against THESE dims — do not re-derive the
        convention elsewhere.
        """
        if self.depthwise:
            if grouped_depthwise(backend):
                return (self.c, self.k, self.count)
            return (self.c, self.k * self.count, self.count)
        return (self.c, self.k, self.d)


@dataclasses.dataclass(frozen=True)
class OpNode:
    """One node of a lowered CNN graph.  Only the fields relevant to
    ``op`` are read; the rest keep their defaults (the builder helpers
    below construct well-formed nodes)."""
    name: str
    op: str
    inputs: Tuple[str, ...] = ()
    cout: int = 0          # conv/fc output channels; input: C_in
    kh: int = 3            # conv/depthwise kernel size
    kw: int = 3
    stride: int = 1        # conv/depthwise stride
    padding: str = "same"  # conv/depthwise/pool: 'same' | 'valid'
    relu: bool = False     # ReLU after the op (post-GEMM activation unit)
    pool: str = "max"      # pool kind: 'max' | 'avg' | 'global'
    pool_size: int = 2
    pool_stride: int = 2
    groups: int = 2        # shuffle groups
    c_lo: int = 0          # slice channel range [c_lo, c_hi)
    c_hi: int = 0


@dataclasses.dataclass(frozen=True)
class OpGraph:
    """Topologically-ordered node tuple; the last node is the output.

    Validated at construction: unique names, known ops, every input
    referencing an EARLIER node, per-op arity.  Hashable by value (all
    fields are frozen/hashable) — a valid static jax.jit argument.
    """
    nodes: Tuple[OpNode, ...]

    def __post_init__(self):
        object.__setattr__(self, "nodes", tuple(self.nodes))
        if not self.nodes:
            raise ValueError("OpGraph needs at least one node")
        seen = set()
        for i, n in enumerate(self.nodes):
            if n.op not in OPS:
                raise ValueError(f"{n.name}: unknown op {n.op!r} "
                                 f"(known: {OPS})")
            if n.name in seen:
                raise ValueError(f"duplicate node name {n.name!r}")
            seen.add(n.name)
            if n.op == "input":
                if i != 0:
                    raise ValueError(
                        f"{n.name}: 'input' must be the first node")
                if n.inputs:
                    raise ValueError(f"{n.name}: 'input' takes no inputs")
                if n.cout < 1:
                    raise ValueError(
                        f"{n.name}: input node carries C_in in cout, "
                        f"got {n.cout}")
                continue
            want = (2 if n.op == "residual_add"
                    else None if n.op == "concat" else 1)
            if want is not None and len(n.inputs) != want:
                raise ValueError(
                    f"{n.name}: op {n.op!r} takes {want} input(s), "
                    f"got {len(n.inputs)}")
            if n.op == "concat" and len(n.inputs) < 2:
                raise ValueError(f"{n.name}: concat needs >= 2 inputs")
            for src in n.inputs:
                if src not in seen:
                    raise ValueError(
                        f"{n.name}: input {src!r} is not an earlier node "
                        f"(graphs are topologically ordered)")
            if n.op in ("conv", "depthwise_conv"):
                if n.kh < 1 or n.kw < 1 or n.stride < 1:
                    raise ValueError(
                        f"{n.name}: kernel {n.kh}x{n.kw} stride {n.stride} "
                        f"must all be >= 1")
                if n.padding not in PADDINGS:
                    raise ValueError(f"{n.name}: padding {n.padding!r} "
                                     f"not in {PADDINGS}")
            if n.op == "conv" and n.cout < 1:
                raise ValueError(f"{n.name}: conv needs cout >= 1")
            if n.op == "fc" and n.cout < 1:
                raise ValueError(f"{n.name}: fc needs cout >= 1")
            if n.op == "pool":
                if n.pool not in POOL_KINDS:
                    raise ValueError(f"{n.name}: pool kind {n.pool!r} "
                                     f"not in {POOL_KINDS}")
                if n.pool != "global" and (n.pool_size < 1
                                          or n.pool_stride < 1):
                    raise ValueError(
                        f"{n.name}: pool_size/pool_stride must be >= 1")
                if n.pool == "avg" and n.padding == "same" \
                        and n.pool_size > 1:
                    raise ValueError(
                        f"{n.name}: 'same'-padded avg pool is ambiguous "
                        f"(padding in the divisor) — use 'valid' or max")
            if n.op == "slice" and not 0 <= n.c_lo < n.c_hi:
                raise ValueError(
                    f"{n.name}: slice needs 0 <= c_lo < c_hi, got "
                    f"[{n.c_lo}, {n.c_hi})")
            if n.op == "shuffle" and n.groups < 1:
                raise ValueError(f"{n.name}: shuffle groups must be >= 1")

    @property
    def input(self) -> OpNode:
        return self.nodes[0]

    @property
    def output(self) -> OpNode:
        return self.nodes[-1]

    @property
    def gemm_nodes(self) -> Tuple[OpNode, ...]:
        return tuple(n for n in self.nodes if n.op in GEMM_OPS)

    def node(self, name: str) -> OpNode:
        for n in self.nodes:
            if n.name == name:
                return n
        raise KeyError(name)


# ---------------------------------------------------------------------------
# Builder helpers (terse, well-formed nodes)
# ---------------------------------------------------------------------------
def input_node(cin: int, name: str = "input") -> OpNode:
    return OpNode(name, "input", cout=cin)


def conv(name, src, cout, kk=3, stride=1, relu=True,
         padding="same") -> OpNode:
    return OpNode(name, "conv", (src,), cout=cout, kh=kk, kw=kk,
                  stride=stride, relu=relu, padding=padding)


def dwconv(name, src, kk=3, stride=1, relu=False,
           padding="same") -> OpNode:
    return OpNode(name, "depthwise_conv", (src,), kh=kk, kw=kk,
                  stride=stride, relu=relu, padding=padding)


def pool(name, src, kind="max", size=2, stride=2,
         padding="valid") -> OpNode:
    return OpNode(name, "pool", (src,), pool=kind, pool_size=size,
                  pool_stride=stride, padding=padding)


def global_avg(name, src) -> OpNode:
    return OpNode(name, "pool", (src,), pool="global")


def residual(name, a, b, relu=True) -> OpNode:
    return OpNode(name, "residual_add", (a, b), relu=relu)


def concat(name, *srcs) -> OpNode:
    return OpNode(name, "concat", tuple(srcs))


def shuffle(name, src, groups=2) -> OpNode:
    return OpNode(name, "shuffle", (src,), groups=groups)


def slice_ch(name, src, lo, hi) -> OpNode:
    return OpNode(name, "slice", (src,), c_lo=lo, c_hi=hi)


def fc(name, src, cout, relu=False) -> OpNode:
    return OpNode(name, "fc", (src,), cout=cout, relu=relu)


# ---------------------------------------------------------------------------
# Spatial arithmetic + shape inference
# ---------------------------------------------------------------------------
def spatial_dims(in_hw) -> Tuple[int, int]:
    """Normalize a spatial-size spec: int -> square, (H, W) -> as given.

    Validates explicitly — a bad spec used to surface as reshape noise
    deep inside the walk."""
    if isinstance(in_hw, (tuple, list)):
        if len(in_hw) != 2:
            raise ValueError(
                f"in_hw must be an int or an (H, W) pair, got "
                f"{tuple(in_hw)!r}")
        h, w = int(in_hw[0]), int(in_hw[1])
    else:
        h = w = int(in_hw)
    if h < 1 or w < 1:
        raise ValueError(f"in_hw must be positive, got {h}x{w}")
    return h, w


def conv_out_dim(size: int, k: int, stride: int, padding: str) -> int:
    """Output extent of one spatial axis (TF/XLA SAME/VALID semantics)."""
    if padding == "same":
        return -(-size // stride)
    if size < k:
        raise ValueError(
            f"'valid' window k={k} does not fit in extent {size} — pad "
            f"the input or use padding='same'")
    return (size - k) // stride + 1


def _pool_out(node: OpNode, h: int, w: int) -> Tuple[int, int]:
    if node.pool == "global":
        return 1, 1
    s, st = node.pool_size, node.pool_stride
    if node.padding == "same":
        return -(-h // st), -(-w // st)
    for dim, tag in ((h, "H"), (w, "W")):
        if dim < s or (dim - s) % st:
            raise ValueError(
                f"{node.name}: 'valid' {s}x{s}/{st} pool does not tile "
                f"{tag}={dim} (needs {tag} >= {s} and ({tag} - {s}) "
                f"divisible by {st}) — odd/indivisible dims must be "
                f"handled explicitly: use padding='same', a global pool, "
                f"or resize the input")
    return (h - s) // st + 1, (w - s) // st + 1


def infer_shapes(graph: OpGraph, in_hw,
                 params: Optional[dict] = None
                 ) -> Dict[str, Tuple[int, int, int]]:
    """Per-node output shapes (H, W, C) for a given input spatial size.

    Channels come from node attrs (``cout``); when ``params`` is given,
    every GEMM weight shape is validated against the inferred one with a
    clear error.
    """
    h, w = spatial_dims(in_hw)
    shapes: Dict[str, Tuple[int, int, int]] = {}
    for n in graph.nodes:
        if n.op == "input":
            shapes[n.name] = (h, w, n.cout)
            continue
        ih, iw, ic = shapes[n.inputs[0]]
        if n.op in ("conv", "depthwise_conv"):
            oh = conv_out_dim(ih, n.kh, n.stride, n.padding)
            ow = conv_out_dim(iw, n.kw, n.stride, n.padding)
            oc = ic if n.op == "depthwise_conv" else n.cout
            want = ((n.kh * n.kw, ic) if n.op == "depthwise_conv"
                    else (n.kh * n.kw * ic, oc))
            shapes[n.name] = (oh, ow, oc)
        elif n.op == "fc":
            oc = n.cout
            want = (ih * iw * ic, oc)
            shapes[n.name] = (1, 1, oc)
        elif n.op == "pool":
            oh, ow = _pool_out(n, ih, iw)
            shapes[n.name] = (oh, ow, ic)
        elif n.op == "residual_add":
            other = shapes[n.inputs[1]]
            if other != (ih, iw, ic):
                raise ValueError(
                    f"{n.name}: residual_add inputs disagree — "
                    f"{n.inputs[0]} is {(ih, iw, ic)} but {n.inputs[1]} "
                    f"is {other}")
            shapes[n.name] = (ih, iw, ic)
        elif n.op == "concat":
            cs = 0
            for src in n.inputs:
                sh, sw, sc = shapes[src]
                if (sh, sw) != (ih, iw):
                    raise ValueError(
                        f"{n.name}: concat inputs disagree spatially — "
                        f"{n.inputs[0]} is {ih}x{iw} but {src} is "
                        f"{sh}x{sw}")
                cs += sc
            shapes[n.name] = (ih, iw, cs)
        elif n.op == "shuffle":
            if ic % n.groups:
                raise ValueError(
                    f"{n.name}: shuffle groups={n.groups} does not divide "
                    f"C={ic}")
            shapes[n.name] = (ih, iw, ic)
        elif n.op == "slice":
            if n.c_hi > ic:
                raise ValueError(
                    f"{n.name}: slice [{n.c_lo}, {n.c_hi}) exceeds C={ic}")
            shapes[n.name] = (ih, iw, n.c_hi - n.c_lo)
        if n.op in GEMM_OPS and params is not None:
            got = tuple(params[n.name].shape)
            if got != want:
                raise ValueError(
                    f"{n.name}: weight shape {got} but the graph at this "
                    f"node implies {want} (in_hw mismatch, or params from "
                    f"a different graph)")
    return shapes


# ---------------------------------------------------------------------------
# im2col (general stride/padding; the stride-1 'same' case is bit-
# identical to the original models.cnn._im2col)
# ---------------------------------------------------------------------------
def pad_window(x: jnp.ndarray, kh: int, kw: int, stride: int,
               padding: str) -> Tuple[jnp.ndarray, Tuple[int, int]]:
    """NHWC -> (the input zero-padded for a kh x kw window, (OH, OW)):
    TF/XLA SAME splits the padding with the odd pixel at the end."""
    n, h, w, c = x.shape
    oh = conv_out_dim(h, kh, stride, padding)
    ow = conv_out_dim(w, kw, stride, padding)
    if padding == "same":
        ph = max((oh - 1) * stride + kh - h, 0)
        pw = max((ow - 1) * stride + kw - w, 0)
        x = jnp.pad(x, ((0, 0), (ph // 2, ph - ph // 2),
                        (pw // 2, pw - pw // 2), (0, 0)))
    return x, (oh, ow)


def im2col(x: jnp.ndarray, kh: int, kw: int, stride: int = 1,
           padding: str = "same") -> Tuple[jnp.ndarray, Tuple[int, int]]:
    """NHWC -> ((N, OH*OW, kh*kw*C) patches, (OH, OW)).

    K is ordered patch-position-major, channel-minor — the same layout
    ``weight_hwio`` expects and build_* initializers produce.  The
    oracle (``graph_apply``) lowers every conv through here; the
    executor does so for convs wider than ``SMALL_CIN_MAX`` channels,
    and builds a stem's patches from codes in this same K order
    (``small_cin_conv``).
    """
    n, c = x.shape[0], x.shape[3]
    x, (oh, ow) = pad_window(x, kh, kw, stride, padding)
    patches = [x[:, i:i + (oh - 1) * stride + 1:stride,
                 j:j + (ow - 1) * stride + 1:stride, :]
               for i in range(kh) for j in range(kw)]
    cols = jnp.concatenate(patches, axis=-1).reshape(n, oh * ow,
                                                     kh * kw * c)
    return cols, (oh, ow)


def depthwise_block_diag(w: jnp.ndarray) -> jnp.ndarray:
    """Expand a compact depthwise weight (kh*kw, C) into the block-
    diagonal GEMM operand (kh*kw*C, C) matching im2col's K layout
    (position-major, channel-minor): B[q*C + c, c] = w[q, c]."""
    kkq, c = w.shape
    eye = jnp.eye(c, dtype=w.dtype)
    return (w[:, :, None] * eye[None, :, :]).reshape(kkq * c, c)


def weight_hwio(node: OpNode, w: jnp.ndarray) -> jnp.ndarray:
    """A node's GEMM weight as the HWIO tensor lax.conv expects."""
    if node.op == "depthwise_conv":
        return w.reshape(node.kh, node.kw, 1, w.shape[-1])
    cin = w.shape[0] // (node.kh * node.kw)
    return w.reshape(node.kh, node.kw, cin, w.shape[-1])


# ---------------------------------------------------------------------------
# Parameter init (weight shapes derived from the graph — one source
# of truth; build_* helpers cannot drift from what the walker reads)
# ---------------------------------------------------------------------------
def init_params(graph: OpGraph, key: jax.Array, in_hw=32,
                dtype=jnp.float32) -> Dict[str, jnp.ndarray]:
    """Glorot-style init of every GEMM node's weight, shapes inferred."""
    shapes = infer_shapes(graph, in_hw)
    params: Dict[str, jnp.ndarray] = {}
    prev: Dict[str, Tuple[int, int, int]] = shapes
    for n in graph.gemm_nodes:
        ih, iw, ic = prev[n.inputs[0]]
        if n.op == "conv":
            shape = (n.kh * n.kw * ic, n.cout)
        elif n.op == "depthwise_conv":
            shape = (n.kh * n.kw, ic)
        else:
            shape = (ih * iw * ic, n.cout)
        key, sub = jax.random.split(key)
        params[n.name] = (jax.random.normal(sub, shape, dtype)
                          / jnp.sqrt(shape[0]))
    return params


# ---------------------------------------------------------------------------
# Analytic GEMM table (what the scheduler/perf model plan against)
# ---------------------------------------------------------------------------
def graph_gemms(graph: OpGraph, in_hw,
                params: Optional[dict] = None) -> List[LayerGemm]:
    """The graph's GEMM-bearing nodes as paper-convention LayerGemms.

    conv:      (OH*OW, kh*kw*C_in, C_out)
    depthwise: count=C instances of (OH*OW, kh*kw, 1) — the paper's
               grouped accounting (models.cnn._dw); how the executor
               runs them is ``LayerGemm.executed``.
    fc:        (1, H*W*C, D)

    Order matches the executor's walk exactly — schedule_cnn over this
    list yields plans the executor consumes positionally.
    """
    shapes = infer_shapes(graph, in_hw, params=params)
    out: List[LayerGemm] = []
    for n in graph.gemm_nodes:
        ih, iw, ic = shapes[n.inputs[0]]
        oh, ow, oc = shapes[n.name]
        if n.op == "conv":
            out.append(LayerGemm(n.name, oh * ow, n.kh * n.kw * ic, oc))
        elif n.op == "depthwise_conv":
            out.append(LayerGemm(n.name, oh * ow, n.kh * n.kw, 1,
                                 count=ic))
        else:
            out.append(LayerGemm(n.name, 1, ih * iw * ic, oc))
    return out


# ---------------------------------------------------------------------------
# Forward walkers
# ---------------------------------------------------------------------------
def _global_mean(x: jnp.ndarray) -> jnp.ndarray:
    """Global average pool as a fixed order of elementwise adds: the rows
    in sequence, then the columns, zero-padded to a power of two, by
    pairwise halving; then times 1/(H*W).

    A reduce sums in the order of the physical layout XLA gives its
    operand, and that layout depends on the surrounding program: on TPU a
    Pallas kernel's output and an XLA dot's operand get different ones.
    Two compilations of one network then disagree in the last bit of the
    pooled value, and so in the classifier's quantize scale.  Elementwise
    adds in a fixed pairing round the same in every program.  The order
    is the one XLA's reduce takes on a TPU v5e for an (N, H, W, C)
    operand with W at most 8 (rows in sequence, the W sublanes by
    halving), so a float32 ``jnp.mean`` over (H, W) there gives the same
    bits: a flipped bit of a pooled value flips a classifier code.
    """
    n, h, w, c = x.shape
    v = x[:, 0]
    for i in range(1, h):
        v = v + x[:, i]
    width = 1 << (w - 1).bit_length()
    v = jnp.pad(v, ((0, 0), (0, width - w), (0, 0)))
    while v.shape[1] > 1:
        half = v.shape[1] // 2
        v = v[:, :half] + v[:, half:]
    return (v * (1.0 / (h * w))).reshape(n, 1, 1, c)


def _apply_pool(node: OpNode, x: jnp.ndarray) -> jnp.ndarray:
    if node.pool == "global":
        return _global_mean(x)
    s, st = node.pool_size, node.pool_stride
    pad = "SAME" if node.padding == "same" else "VALID"
    if node.pool == "max":
        return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max,
                                     (1, s, s, 1), (1, st, st, 1), pad)
    return jax.lax.reduce_window(x, 0.0, jax.lax.add, (1, s, s, 1),
                                 (1, st, st, 1), pad) / float(s * s)


def _apply_shuffle(node: OpNode, x: jnp.ndarray) -> jnp.ndarray:
    n, h, w, c = x.shape
    g = node.groups
    return x.reshape(n, h, w, g, c // g).swapaxes(3, 4).reshape(n, h, w, c)


def _apply_glue(node: OpNode, a: jnp.ndarray,
                vals: Dict[str, jnp.ndarray]) -> jnp.ndarray:
    """The non-GEMM ops, shared by BOTH walkers (graph_forward and
    direct_forward) — glue semantics cannot diverge between the lowered
    path and the direct reference."""
    if node.op == "pool":
        return _apply_pool(node, a)
    if node.op == "residual_add":
        return a + vals[node.inputs[1]]
    if node.op == "concat":
        return jnp.concatenate([vals[s] for s in node.inputs], axis=-1)
    if node.op == "shuffle":
        return _apply_shuffle(node, a)
    if node.op == "slice":
        return a[..., node.c_lo:node.c_hi]
    raise ValueError(f"unknown op {node.op!r}")    # pragma: no cover


def graph_forward(params: dict, x: jnp.ndarray, graph: OpGraph,
                  mm: Callable[[jnp.ndarray, jnp.ndarray, int, OpNode],
                               jnp.ndarray],
                  dw: Optional[Callable[[jnp.ndarray, jnp.ndarray, int,
                                         OpNode], jnp.ndarray]] = None,
                  small_cin: Optional[Callable[[jnp.ndarray, jnp.ndarray,
                                                int, OpNode],
                                               jnp.ndarray]] = None
                  ) -> Dict[str, jnp.ndarray]:
    """Walk the graph; returns every node's output by name.

    ``mm(cols2d, weight, gemm_index, node)`` runs one lowered GEMM —
    the executor plugs the photonic kernel + per-layer plan/noise key in
    here; ``graph_apply`` plugs a plain (or photonic-reference) matmul.
    ``dw(x, weight, gemm_index, node)``, when given, runs each depthwise
    node on its (N, H, W, C) input with its compact (kh*kw, C) weight
    and returns (N, OH, OW, C); without it depthwise nodes run as their
    block-diagonal GEMM through ``mm``.
    ``small_cin(x, weight, gemm_index, node)``, when given, runs each
    conv that ``small_cin_conv`` routes (at most ``SMALL_CIN_MAX`` input
    channels) on its (N, H, W, C) input with its (kh*kw*C, D) weight and
    returns (N, OH, OW, D); without it, and for wider convs, the conv
    runs as its ``im2col`` GEMM through ``mm``.
    All shape bookkeeping is static Python, so the walk traces into a
    single jax.jit program with zero host syncs.  Each node's body runs
    under ``jax.named_scope(node.name)``, so every device operation of a
    trace names its node in its ``op_name`` (metadata only: the program
    and its bits are the same).
    """
    n = x.shape[0]
    vals: Dict[str, jnp.ndarray] = {}
    gi = 0
    for node in graph.nodes:
        if node.op == "input":
            vals[node.name] = x
            continue
        a = vals[node.inputs[0]]
        with jax.named_scope(node.name):
            if node.op == "depthwise_conv" and dw is not None:
                y = dw(a, params[node.name], gi, node)
                gi += 1
            elif small_cin is not None and small_cin_conv(node,
                                                          a.shape[-1]):
                y = small_cin(a, params[node.name], gi, node)
                gi += 1
            elif node.op in ("conv", "depthwise_conv"):
                wgt = params[node.name]
                w2d = (depthwise_block_diag(wgt)
                       if node.op == "depthwise_conv" else wgt)
                cols, (oh, ow) = im2col(a, node.kh, node.kw, node.stride,
                                        node.padding)
                out = mm(cols.reshape(-1, cols.shape[-1]), w2d, gi, node)
                y = out.reshape(n, oh, ow, w2d.shape[-1])
                gi += 1
            elif node.op == "fc":
                y = mm(a.reshape(n, -1), params[node.name], gi, node)
                gi += 1
            else:
                y = _apply_glue(node, a, vals)
            if node.relu:
                y = jax.nn.relu(y)
        vals[node.name] = y
    return vals


def graph_apply(params: dict, x: jnp.ndarray, graph: OpGraph,
                matmul: Optional[Callable] = None) -> jnp.ndarray:
    """Forward pass of a lowered graph with a plain ``matmul(a, w)``
    (default exact; pass the photonic simulation for noisy numerics)."""
    base = matmul or (lambda a, w: a @ w)
    vals = graph_forward(params, x, graph,
                         lambda a, w, i, node: base(a, w))
    return vals[graph.output.name]


def direct_forward(params: dict, x: jnp.ndarray,
                   graph: OpGraph) -> jnp.ndarray:
    """Reference forward that does NOT lower to GEMMs: convolutions via
    jax.lax.conv_general_dilated (depthwise via feature_group_count).
    The property suite pins ``graph_apply == direct_forward`` — i.e. the
    im2col/block-diagonal lowering itself is correct for every stride,
    padding, rectangle and branch structure."""
    vals: Dict[str, jnp.ndarray] = {}
    for node in graph.nodes:
        if node.op == "input":
            vals[node.name] = x
            continue
        a = vals[node.inputs[0]]
        if node.op in ("conv", "depthwise_conv"):
            w = weight_hwio(node, params[node.name])
            y = jax.lax.conv_general_dilated(
                a, w, (node.stride, node.stride), node.padding.upper(),
                dimension_numbers=("NHWC", "HWIO", "NHWC"),
                feature_group_count=(a.shape[-1]
                                     if node.op == "depthwise_conv"
                                     else 1))
        elif node.op == "fc":
            y = a.reshape(a.shape[0], -1) @ params[node.name]
        else:
            y = _apply_glue(node, a, vals)
        if node.relu:
            y = jax.nn.relu(y)
        vals[node.name] = y
    return vals[graph.output.name]
