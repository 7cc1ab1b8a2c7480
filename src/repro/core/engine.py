"""Xenos runtime: executes an (optimized) computation graph.

Three execution modes mirror the paper's Fig.-7 ablation:

* ``vanilla`` — the unoptimized dataflow: every operator is dispatched
  separately, unfused, and intermediates are *stored* in the mismatched
  layout (NCHW) while every operator *reads* NHWC — reproducing the Figure-2
  write/read-order mismatch as explicit transposes and per-op HBM (host)
  round-trips.
* ``ho`` — horizontal optimization only: DOS split plans annotate every
  compute op and large contractions execute in L2-sized chunks; dispatch is
  still per-op and the layout mismatch remains (VO is off).  The across-unit
  parallel speedup itself is reported by the roofline model (this container
  has one core — DESIGN.md §2).
* ``xenos`` — HO + VO: the linked graph executes one *fused region per link
  group* (a single jitted computation: intermediates never materialize, the
  producer's write order is the consumer's read order) and all layouts are
  matched (no transposes).

The engine is also where linked ops (``cbra``/``cbrm``) may lower to the
Pallas kernels in ``repro.kernels`` — the ``linked_matmul`` site of a
``KernelPlan`` (``core.pipeline``), demonstrating the kernel-level version
of operator linking.  Pass ``plan=`` (or let ``kernel_select`` decide) to
route it.

Graphs should be optimized through the pass manager (core/pipeline.py)
rather than by calling stages directly; ``build_engine`` below does both
steps — per-mode pipeline then Engine — and returns the PassReport.
"""
from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .dos import SplitPlan
from .graph import Graph, OpNode

# ---------------------------------------------------------------------------
# Parameter initialization & CBR folding
# ---------------------------------------------------------------------------

def init_params(g: Graph, seed: int = 0) -> dict[str, jax.Array]:
    rng = np.random.default_rng(seed)
    out: dict[str, jax.Array] = {}
    for name in g.params:
        spec = g.tensors[name]
        if name.endswith(".scale"):
            arr = np.abs(rng.normal(1.0, 0.1, spec.shape))
        elif name.endswith((".shift", ".b")):
            arr = rng.normal(0.0, 0.02, spec.shape)
        else:
            fan_in = int(np.prod(spec.shape[:-1])) or 1
            arr = rng.normal(0.0, (2.0 / fan_in) ** 0.5, spec.shape)
        out[name] = jnp.asarray(arr, jnp.float32)
    return out


def fold_cbr(node: OpNode, params: dict[str, jax.Array]) -> tuple[jax.Array, jax.Array]:
    """Fold BN scale/shift (+bias) into the conv weight/bias — exact at inference."""
    w = params[node.params[0]]
    out_c = w.shape[-1] if node.op_type != "dwconv" and not node.attrs.get("depthwise") \
        else w.shape[2]
    scale = jnp.ones((out_c,), jnp.float32)
    shift = jnp.zeros((out_c,), jnp.float32)
    for p in node.params[1:]:
        if p.endswith(".scale"):
            scale = scale * params[p]
        elif p.endswith(".shift") or p.endswith(".b"):
            shift = shift + params[p]
    if node.attrs.get("depthwise"):
        w = w * scale[None, None, :, None]
    else:
        w = w * scale[None, None, None, :]
    return w, shift


# ---------------------------------------------------------------------------
# Operator semantics (NHWC reference implementations)
# ---------------------------------------------------------------------------

def _conv(x, w, stride: int, padding: str, depthwise: bool = False):
    groups = x.shape[-1] if depthwise else 1
    if depthwise:
        # HWIO with I=1 replicated per group: reshape (k,k,C,1)->(k,k,1,C)
        w = jnp.transpose(w, (0, 1, 3, 2))
    return lax.conv_general_dilated(
        x, w, window_strides=(stride, stride), padding=padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=groups)


def _pool(x, kind: str, ksize: int = 2, stride: int | None = None):
    if kind == "global_avg":
        return jnp.mean(x, axis=(1, 2), keepdims=True)
    stride = stride or ksize
    window = (1, ksize, ksize, 1)
    strides = (1, stride, stride, 1)
    if kind == "max":
        return lax.reduce_window(x, -jnp.inf, lax.max, window, strides, "VALID")
    s = lax.reduce_window(x, 0.0, lax.add, window, strides, "VALID")
    return s / (ksize * ksize)


def _matmul_split(x, w, b, plan: SplitPlan | None):
    """Matmul with HO param split: contract in K-chunks sized to L2 (§4.2.2)."""
    if plan is None or not plan.param_chunks:
        return x @ w + b
    k_chunks = plan.param_chunks.get("K", 1)
    if k_chunks > 1 and w.shape[1] % k_chunks == 0:
        # output-channel split: y_i = W_i x + B_i, joined by concat (Eq. 1)
        ws = jnp.split(w, k_chunks, axis=1)
        bs = jnp.split(b, k_chunks, axis=0)
        return jnp.concatenate([x @ wi + bi for wi, bi in zip(ws, bs)], axis=-1)
    c_chunks = plan.param_chunks.get("inC", 1)
    if c_chunks > 1 and w.shape[0] % c_chunks == 0:
        xs = jnp.split(x, c_chunks, axis=-1)
        ws = jnp.split(w, c_chunks, axis=0)
        acc = b
        for xi, wi in zip(xs, ws):  # inC split needs the extra reduction
            acc = acc + xi @ wi
        return acc
    return x @ w + b


def eval_op(node: OpNode, inputs: list[jax.Array],
            params: dict[str, jax.Array],
            linked_backend: str = "xla") -> list[jax.Array]:
    """Evaluate one op in NHWC semantics.  ``linked_backend`` is the
    ``linked_matmul`` site of a ``KernelPlan``: ``"pallas"`` lowers
    eligible linked ``cbra`` ops to the fused kernel."""
    t = node.op_type
    a = node.attrs
    plan: SplitPlan | None = node.dataflow.get("split_plan")
    x = inputs[0] if inputs else None

    if t in ("conv", "dwconv"):
        w = params[node.params[0]]
        y = _conv(x, w, a.get("stride", 1), a.get("padding", "SAME"),
                  depthwise=(t == "dwconv"))
        return [y]
    if t == "cbr":
        w, b = fold_cbr(node, params)
        y = _conv(x, w, a.get("stride", 1), a.get("padding", "SAME"),
                  depthwise=a.get("depthwise", False))
        return [jax.nn.relu(y + b)]
    if t in ("cbra", "cbrm"):
        pool_attrs = a.get("pool", {})
        # the kernel's domain: a 1x1 stride-1 dense conv and a 2x2
        # stride-2 pool over a map of whole 2x2 squares
        if linked_backend == "pallas" and t == "cbra" and a.get("ksize", 1) == 1 \
                and a.get("stride", 1) == 1 and not a.get("depthwise", False) \
                and pool_attrs.get("ksize", 2) == 2 \
                and pool_attrs.get("stride") in (None, 2) \
                and x.shape[1] % 2 == 0 and x.shape[2] % 2 == 0:
            from repro.kernels.linked_cbr_pool import ops as cbra_ops
            w, b = fold_cbr(node, params)
            return [cbra_ops.cbr_avgpool(x, w, b)]
        w, b = fold_cbr(node, params)
        y = jax.nn.relu(_conv(x, w, a.get("stride", 1), a.get("padding", "SAME"),
                              depthwise=a.get("depthwise", False)) + b)
        kind = "avg" if t == "cbra" else "max"
        return [_pool(y, kind, pool_attrs.get("ksize", 2), pool_attrs.get("stride"))]
    if t == "bn":
        scale, shift = params[node.params[0]], params[node.params[1]]
        return [x * scale + shift]
    if t == "bias":
        return [x + params[node.params[0]]]
    if t == "relu":
        return [jax.nn.relu(x)]
    if t == "gampool":
        return [_pool(x, a["kind"], a.get("ksize", 2), a.get("stride"))]
    if t == "matmul":
        if not node.params:  # dynamic two-operand form (attention scores etc.)
            return [inputs[0] @ inputs[1]]
        w, b = params[node.params[0]], params[node.params[1]]
        return [_matmul_split(x, w, b, plan)]
    if t == "add":
        return [inputs[0] + inputs[1]]
    if t == "mul":
        return [inputs[0] * inputs[1]]
    if t == "mac":
        return [inputs[0] * inputs[1] + inputs[2]]
    if t == "concat":
        return [jnp.concatenate(inputs, axis=a.get("axis", -1))]
    if t == "split":
        return list(jnp.split(x, a["sections"], axis=a.get("axis", -1)))
    if t == "flatten":
        return [x.reshape(x.shape[0], -1)]
    if t == "softmax":
        return [jax.nn.softmax(x, axis=-1)]
    if t == "transpose":
        return [jnp.transpose(x, a.get("perm"))]
    raise NotImplementedError(t)


# ---------------------------------------------------------------------------
# Engine
# ---------------------------------------------------------------------------

def _to_storage(x: jax.Array) -> jax.Array:
    """NHWC compute layout -> NCHW storage layout (the mismatched write)."""
    return jnp.transpose(x, (0, 3, 1, 2)) if x.ndim == 4 else x


def _from_storage(x: jax.Array) -> jax.Array:
    return jnp.transpose(x, (0, 2, 3, 1)) if x.ndim == 4 else x


class Engine:
    """Executes a graph in one of the three ablation modes."""

    def __init__(self, g: Graph, mode: str = "xenos", plan=None):
        from .pipeline import KernelPlan
        assert mode in ("vanilla", "ho", "xenos"), mode
        self.graph = g
        self.mode = mode
        #: KernelPlan routing the linked-op lowering; defaults to the
        #: pure-XLA seed plan (``KernelPlan()``).
        self.plan = plan if plan is not None else KernelPlan()
        self._op_jits: dict[str, Callable] = {}
        self._group_jit: Callable | None = None

    # -- fused whole-graph function (xenos mode) -----------------------------
    def _build_fused(self) -> Callable:
        g = self.graph

        def fn(params: dict[str, jax.Array], *inputs: jax.Array):
            env: dict[str, jax.Array] = dict(zip(g.inputs, inputs))
            for node in g.nodes:
                ins = [env[t] for t in node.inputs]
                outs = eval_op(node, ins, params, self.plan.linked_matmul)
                env.update(zip(node.outputs, outs))
            return tuple(env[t] for t in g.outputs)

        return jax.jit(fn)

    def __call__(self, params: dict[str, jax.Array], *inputs: jax.Array,
                 block: bool = True):
        if self.mode == "xenos":
            if self._group_jit is None:
                self._group_jit = self._build_fused()
            out = self._group_jit(params, *inputs)
            if block:
                jax.block_until_ready(out)
            return out
        return self._run_per_op(params, inputs, block)

    # -- per-op dispatch with layout mismatch (vanilla / ho modes) -----------
    def _op_fn(self, node: OpNode) -> Callable:
        if node.name not in self._op_jits:
            def fn(params, *ins, _node=node):
                ins = [_from_storage(x) for x in ins]          # mismatched read
                outs = eval_op(_node, list(ins), params, "xla")
                return tuple(_to_storage(o) for o in outs)     # mismatched write
            self._op_jits[node.name] = jax.jit(fn)
        return self._op_jits[node.name]

    def _run_per_op(self, params, inputs, block: bool):
        g = self.graph
        env: dict[str, jax.Array] = {
            name: _to_storage(x) for name, x in zip(g.inputs, inputs)}
        for node in g.nodes:
            ins = [env[t] for t in node.inputs]
            outs = self._op_fn(node)(params, *ins)
            if block:
                jax.block_until_ready(outs)  # per-op dispatch boundary
            env.update(zip(node.outputs, outs))
        result = tuple(_from_storage(env[t]) for t in g.outputs)
        if block:
            jax.block_until_ready(result)
        return result


def execute(g: Graph, params: dict[str, jax.Array], inputs: dict[str, Any],
            mode: str = "xenos", plan=None):
    """One-shot functional execution (used by tests)."""
    eng = Engine(g, mode, plan)
    ins = [jnp.asarray(inputs[name]) for name in g.inputs]
    return eng(params, *ins)


def build_engine(g: Graph, mode: str = "xenos",
                 device=None, plan=None):
    """Optimize ``g`` for ``mode`` through the pass pipeline, then wrap it.

    This is the one-stop path callers should use instead of hand-wiring
    ``fuse_cbr -> link -> dos`` themselves: ``vanilla`` runs no passes,
    ``ho`` runs ``dos_split`` only, ``xenos`` the full default pipeline.
    Returns ``(Engine, PassReport)`` — the report carries per-pass wall
    times, node/edge deltas and the modeled cost saving.  ``plan``
    (``KernelPlan`` or None for the seed plan) routes the linked-op
    lowering — run the ``kernel_select`` pass to derive one.
    """
    from .pipeline import optimize_for_mode
    opt, report = optimize_for_mode(g, mode, device)
    return Engine(opt, mode, plan), report
