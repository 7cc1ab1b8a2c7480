"""Unified optimization pass manager (the §4.4 "automatic workflow" as a subsystem).

The paper's pitch is that dataflow optimization is *automatic*: pattern
identification, vertical linking (§4.1), horizontal split (§4.2) and the
d-Xenos planner (§5) run over the computation graph without per-model
hand-wiring.  This module is that workflow as a first-class object:

  * every optimization stage is a registered :class:`Pass` with a name,
    a description, and declared post-invariants;
  * :func:`optimize` is the single entry point — it runs a pass list (or a
    numbered level), verifies the graph after every rewrite, and returns the
    optimized graph together with a structured :class:`PassReport` (per-pass
    wall time, node/edge deltas, link-group and split-plan summaries, and the
    modeled cost savings of the whole pipeline);
  * :func:`verify_graph` is the post-pass checker: dangling edges, producer
    consistency, layout validity, and link-group well-formedness.  A rewrite
    that corrupts the graph raises :class:`PassVerificationError` at the pass
    that introduced it, not three stages later.

Registered passes (see the bottom of this file):

  ==============  ============================================================
  ``fuse_cbr``        preprocessing fusion Conv+Bn(+Bias)+Relu -> CBR (§3)
  ``link_operators``  vertical optimization: Table-1 linking (§4.1)
  ``dos_split``       horizontal optimization: DSP-aware operator split (§4.2)
  ``dxenos_plan``     d-Xenos partition-scheme planning, Algorithm 1 (§5)
  ``serve_schedule``  serving-schedule planning (slots/chunk/KV pool/spec_k)
  ``kernel_select``   kernel routing: cost model + timings -> ``KernelPlan``
  ==============  ============================================================

Levels are cumulative pass prefixes (``dxenos_plan`` is opt-in because it
needs an ``n_devices`` choice):

  ==========  =================================================
  ``O0``      no passes (the Fig.-7 *vanilla* dataflow)
  ``O1``      ``fuse_cbr``
  ``O2``      + ``link_operators``  (VO; Fig.-7 *xenos* minus HO)
  ``O3``      + ``dos_split``       (VO + HO; the default)
  ==========  =================================================

New optimizations (fusion patterns, caching, multi-backend lowering) are
drop-in: define a function ``Graph -> Graph`` and register it with
:func:`register_pass` / the :func:`graph_pass` decorator.
"""
from __future__ import annotations

import dataclasses
import hashlib
import time
from typing import Any, Callable, Iterable, Sequence

from jax.profiler import TraceAnnotation

from . import costmodel as cm
from . import dos, linking
from .dos import DeviceSpec
from .graph import Graph, LAYOUTS, OP_VOCABULARY, OpNode


# ---------------------------------------------------------------------------
# Errors
# ---------------------------------------------------------------------------

class PipelineError(ValueError):
    """Bad pipeline configuration (unknown pass / level)."""


class PassVerificationError(RuntimeError):
    """A pass produced a graph that fails :func:`verify_graph`."""

    def __init__(self, pass_name: str, problems: Sequence[str]):
        self.pass_name = pass_name
        self.problems = list(problems)
        detail = "\n  - ".join(self.problems)
        super().__init__(
            f"pass {pass_name!r} corrupted the graph:\n  - {detail}")


# ---------------------------------------------------------------------------
# Graph verification
# ---------------------------------------------------------------------------

def verify_graph(g: Graph) -> list[str]:
    """Structural checks every rewrite must preserve.  Returns problems found.

    * every tensor a node reads/writes exists, and producers are consistent
      (no dangling edges after a splice);
    * nodes appear in topological order and op types stay inside the closed
      Table-3 vocabulary;
    * rank-4 feature maps carry a known layout (``NHWC``/``NCHW``; non-rank-4
      tensors use the empty layout);
    * link groups are well-formed: at least two members, and the members form
      a connected region of the graph (linking is defined on *adjacent*
      operators — a group split across unrelated subgraphs is a bad rewrite).
    """
    problems: list[str] = []
    node_names = {n.name for n in g.nodes}
    if len(node_names) != len(g.nodes):
        problems.append("duplicate node names")

    # -- tensor / edge consistency ------------------------------------------
    produced: set[str] = set(g.inputs) | set(g.params)
    for n in g.nodes:
        for t in list(n.inputs) + list(n.params):
            if t not in g.tensors:
                problems.append(f"{n.name} reads dangling tensor {t!r}")
            elif t not in produced:
                spec = g.tensors[t]
                if spec.producer is None:
                    problems.append(
                        f"{n.name} reads {t!r} which is neither an input, a "
                        f"param, nor produced by any node")
                else:
                    problems.append(
                        f"graph not topologically ordered: {n.name} reads "
                        f"{t!r} before its producer {spec.producer!r} runs")
        for t in n.outputs:
            if t not in g.tensors:
                problems.append(f"{n.name} writes unregistered tensor {t!r}")
            elif g.tensors[t].producer != n.name:
                problems.append(
                    f"tensor {t!r} names producer {g.tensors[t].producer!r} "
                    f"but is written by {n.name}")
            produced.add(t)
        if n.op_type not in OP_VOCABULARY:
            problems.append(f"{n.name} has op_type {n.op_type!r} outside the "
                            f"Table-3 vocabulary")
    for t in g.outputs:
        if t not in g.tensors:
            problems.append(f"graph output {t!r} is a dangling tensor")
        elif t not in produced:
            problems.append(f"graph output {t!r} is never produced")

    # -- tensor spec sanity: shapes and layouts ------------------------------
    for t, spec in g.tensors.items():
        if any((not isinstance(s, int)) or s <= 0 for s in spec.shape):
            problems.append(f"tensor {t!r} has non-positive shape {spec.shape}")
        if spec.rank == 4 and spec.layout and spec.layout not in LAYOUTS:
            problems.append(f"tensor {t!r} has unknown layout {spec.layout!r}")
        if spec.producer is not None and spec.producer not in node_names:
            problems.append(
                f"tensor {t!r} claims producer {spec.producer!r} which is "
                f"not a node in the graph")

    # -- link-group well-formedness -----------------------------------------
    groups = linking.link_groups(g)
    for gid, members in groups.items():
        if len(members) < 2:
            problems.append(
                f"link_group {gid} has a single member "
                f"({members[0].name}); linking is defined on op *chains*")
            continue
        member_names = {m.name for m in members}
        # connected: the members must form one producer/consumer-connected
        # region (chains and shortcut joins both qualify; unrelated ops
        # sharing a gid do not).
        frontier = [members[0].name]
        reached = {members[0].name}
        while frontier:
            m = g.node_by_name(frontier.pop())
            neighbours = {p.name for p in g.predecessors(m)}
            neighbours |= {s.name for s in g.successors(m)}
            for nb in neighbours & member_names - reached:
                reached.add(nb)
                frontier.append(nb)
        if reached != member_names:
            problems.append(
                f"link_group {gid} is not a connected region: "
                f"{sorted(member_names - reached)} detached from "
                f"{sorted(reached)}")
    return problems


# ---------------------------------------------------------------------------
# Pass + registry
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class PassContext:
    """Per-run state handed to every pass."""

    device: DeviceSpec
    options: dict[str, Any] = dataclasses.field(default_factory=dict)
    #: pass-populated artifacts (e.g. the chosen d-Xenos scheme); merged into
    #: the pass's PassRecord.summary after it runs.
    artifacts: dict[str, Any] = dataclasses.field(default_factory=dict)


@dataclasses.dataclass(frozen=True)
class Pass:
    """One registered optimization stage."""

    name: str
    fn: Callable[[Graph, PassContext], Graph]
    description: str
    #: invariants the pass declares beyond verify_graph's structural checks;
    #: each is a named predicate Graph -> bool, checked after the pass runs.
    invariants: tuple[tuple[str, Callable[[Graph], bool]], ...] = ()
    #: extracts a human-facing summary dict from (before, after) graphs.
    summarize: Callable[[Graph, Graph], dict[str, Any]] | None = None


REGISTRY: dict[str, Pass] = {}

#: cumulative optimization levels (dxenos_plan is opt-in, see module docstring)
LEVELS: dict[int, tuple[str, ...]] = {
    0: (),
    1: ("fuse_cbr",),
    2: ("fuse_cbr", "link_operators"),
    3: ("fuse_cbr", "link_operators", "dos_split"),
}
DEFAULT_LEVEL = 3


def register_pass(p: Pass) -> Pass:
    if p.name in REGISTRY:
        raise PipelineError(f"pass {p.name!r} is already registered")
    REGISTRY[p.name] = p
    return p


def unregister_pass(name: str) -> None:
    REGISTRY.pop(name, None)


def graph_pass(name: str, description: str, *,
               invariants: Iterable[tuple[str, Callable[[Graph], bool]]] = (),
               summarize: Callable[[Graph, Graph], dict[str, Any]] | None = None):
    """Decorator form of :func:`register_pass` for drop-in stages."""

    def wrap(fn: Callable[[Graph, PassContext], Graph]):
        register_pass(Pass(name, fn, description, tuple(invariants), summarize))
        return fn

    return wrap


def resolve_passes(level: int | None = None,
                   passes: Sequence[str] | None = None) -> list[Pass]:
    """Pass list for an explicit ``passes`` selection or a numbered level."""
    if passes is not None:
        names = list(passes)
    else:
        lvl = DEFAULT_LEVEL if level is None else level
        if lvl not in LEVELS:
            raise PipelineError(f"unknown level {lvl!r}; have {sorted(LEVELS)}")
        names = list(LEVELS[lvl])
    out = []
    for name in names:
        if name not in REGISTRY:
            raise PipelineError(
                f"unknown pass {name!r}; registered: {sorted(REGISTRY)}")
        out.append(REGISTRY[name])
    return out


# ---------------------------------------------------------------------------
# Reports
# ---------------------------------------------------------------------------

def _edge_count(g: Graph) -> int:
    return sum(len(n.inputs) for n in g.nodes)


@dataclasses.dataclass
class PassRecord:
    """What one pass did to the graph."""

    name: str
    wall_s: float
    nodes_before: int
    nodes_after: int
    edges_before: int
    edges_after: int
    verified: bool
    summary: dict[str, Any] = dataclasses.field(default_factory=dict)

    @property
    def node_delta(self) -> int:
        return self.nodes_after - self.nodes_before

    def as_dict(self) -> dict[str, Any]:
        return {**dataclasses.asdict(self), "node_delta": self.node_delta}


@dataclasses.dataclass
class PassReport:
    """Structured result of one :func:`optimize` run."""

    graph_name: str
    device: str
    passes: list[PassRecord] = dataclasses.field(default_factory=list)
    total_s: float = 0.0
    #: modeled single-unit serial roofline time (costmodel) before the first
    #: pass and after the last, with linking credited — the quantitative
    #: content of Fig. 7's HO/VO reductions.
    modeled_before_s: float = 0.0
    modeled_after_s: float = 0.0
    #: True when this report came out of the pass-result cache: the pipeline
    #: did not run again for this (graph, passes, options, device) key and
    #: the per-pass records describe the original (cached) run.
    cache_hit: bool = False

    @property
    def modeled_saving(self) -> float:
        """Fraction of modeled serial time removed by the pipeline."""
        if self.modeled_before_s <= 0:
            return 0.0
        return 1.0 - self.modeled_after_s / self.modeled_before_s

    def record(self, rec: PassRecord) -> None:
        self.passes.append(rec)
        self.total_s += rec.wall_s

    def as_dict(self) -> dict[str, Any]:
        return {
            "graph": self.graph_name, "device": self.device,
            "total_s": self.total_s,
            "modeled_before_s": self.modeled_before_s,
            "modeled_after_s": self.modeled_after_s,
            "modeled_saving": self.modeled_saving,
            "cache_hit": self.cache_hit,
            "passes": [p.as_dict() for p in self.passes],
        }

    def format(self) -> str:
        """Human-readable table (what the examples and Table-2 bench print)."""
        lines = [f"PassReport[{self.graph_name} @ {self.device}] "
                 f"total {self.total_s * 1e3:.2f} ms, modeled saving "
                 f"{100 * self.modeled_saving:.1f}%"
                 f"{' (cache hit)' if self.cache_hit else ''}"]
        for p in self.passes:
            extras = "".join(f" {k}={v}" for k, v in p.summary.items())
            lines.append(
                f"  {p.name:16s} {p.wall_s * 1e3:7.2f} ms  "
                f"nodes {p.nodes_before:3d} -> {p.nodes_after:3d}  "
                f"edges {p.edges_before:3d} -> {p.edges_after:3d}"
                f"{extras}")
        return "\n".join(lines)


# ---------------------------------------------------------------------------
# Timing helper (shared with the serving engine's stage instrumentation)
# ---------------------------------------------------------------------------

class _Stage:
    """One timed enter/exit of a named stage (see StageTimer)."""

    __slots__ = ("_timer", "_name", "_t0", "_span")

    def __init__(self, timer: "StageTimer", name: str):
        self._timer = timer
        self._name = name

    def __enter__(self):
        t = self._timer
        if self._name.startswith("."):
            self._name = t._open[-1] + self._name
        t._open.append(self._name)
        self._span = TraceAnnotation(t.prefix + self._name)
        self._span.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dt = time.perf_counter() - self._t0
        self._span.__exit__(*exc)
        t = self._timer
        t._open.pop()
        t.totals[self._name] = t.totals.get(self._name, 0.0) + dt
        t.counts[self._name] = t.counts.get(self._name, 0) + 1
        return False


class StageTimer:
    """Context-manager timer: accumulates host wall time and calls per
    named stage, and writes each stage as a span ``prefix + name`` into any
    active profiler session (``jax.profiler.trace``), on the device
    trace's clock.  With no session the span costs about a microsecond.

    A name that starts with ``.`` is a child of the innermost open stage:
    ``stage(".wait")`` inside ``stage("decode")`` is ``decode.wait``."""

    def __init__(self, prefix: str = "") -> None:
        self.prefix = prefix
        self.totals: dict[str, float] = {}
        self.counts: dict[str, int] = {}
        self._open: list[str] = []

    def stage(self, name: str) -> _Stage:
        return _Stage(self, name)

    def as_dict(self) -> dict[str, dict[str, float]]:
        return {k: {"total_s": v, "calls": self.counts[k],
                    "mean_s": v / self.counts[k]}
                for k, v in self.totals.items()}


# ---------------------------------------------------------------------------
# Pass-result caching
# ---------------------------------------------------------------------------

def graph_fingerprint(g: Graph) -> str:
    """Stable content hash of a graph: structure, shapes, attrs and the
    dataflow metadata passes rewrite.  Two graphs with the same fingerprint
    produce the same pipeline output for the same pass list and options."""
    h = hashlib.sha256()
    h.update(repr((g.name, g.inputs, g.params, g.outputs)).encode())
    for n in g.nodes:
        h.update(repr((n.name, n.op_type, n.inputs, n.outputs, n.params,
                       sorted(n.attrs.items(), key=lambda kv: kv[0]),
                       sorted(n.dataflow.items(), key=lambda kv: kv[0]),
                       )).encode())
    for t in sorted(g.tensors):
        spec = g.tensors[t]
        h.update(repr((t, spec.shape, spec.dtype, spec.layout,
                       spec.producer)).encode())
    return h.hexdigest()


#: (graph_fingerprint, pass identities, options, device, verify) ->
#: (optimized graph, report).  Bounded FIFO; see :func:`optimize`.
_OPTIMIZE_CACHE: dict[tuple, tuple[Graph, PassReport]] = {}
_OPTIMIZE_CACHE_MAX = 128


def clear_optimize_cache() -> None:
    _OPTIMIZE_CACHE.clear()


def _cache_key(g: Graph, plist: list[Pass], options: dict[str, Any],
               device: DeviceSpec, verify: bool) -> tuple:
    # id(p.fn) distinguishes a re-registered pass reusing an old name
    return (graph_fingerprint(g),
            tuple((p.name, id(p.fn)) for p in plist),
            repr(sorted(options.items(), key=lambda kv: kv[0])),
            repr(device), verify)


# ---------------------------------------------------------------------------
# The entry point
# ---------------------------------------------------------------------------

def _modeled_serial_s(g: Graph, device: DeviceSpec, linked: bool) -> float:
    flops = sum(cm.op_flops(n, g.tensors) for n in g.nodes)
    byts = sum(cm.op_bytes(n, g.tensors, linked=linked) for n in g.nodes)
    return cm.roofline(flops, byts, 0.0, chips=1).serial_s


def optimize(g: Graph, device: DeviceSpec | None = None, *,
             level: int | None = None, passes: Sequence[str] | None = None,
             options: dict[str, Any] | None = None,
             verify: bool = True, cache: bool = True) -> tuple[Graph, PassReport]:
    """Run the optimization pipeline; returns ``(optimized_graph, report)``.

    ``level`` selects a cumulative pass prefix (default ``O3`` = fuse + link
    + DOS split); ``passes`` overrides with an explicit ordered list of
    registered pass names.  ``options`` is pass-visible configuration (e.g.
    ``n_devices``/``sync`` for ``dxenos_plan``).  With ``verify=True`` every
    pass's output graph is checked by :func:`verify_graph` plus the pass's
    own declared invariants, raising :class:`PassVerificationError` on the
    first corrupted rewrite.

    Results are memoized on ``(graph_fingerprint, passes, options, device)``
    (``cache=False`` opts out): a repeated call returns a clone of the cached
    graph and a report with ``cache_hit=True`` without re-running any pass —
    this is what lets the serving scheduler re-plan every N ticks for free.
    """
    device = device or DeviceSpec()
    ctx = PassContext(device=device, options=dict(options or {}))
    plist = resolve_passes(level, passes)

    key: tuple | None = None
    if cache:
        key = _cache_key(g, plist, ctx.options, device, verify)
        hit = _OPTIMIZE_CACHE.get(key)
        if hit is not None:
            cached_graph, cached_report = hit
            return cached_graph.clone(), dataclasses.replace(
                cached_report, passes=list(cached_report.passes),
                cache_hit=True)

    report = PassReport(graph_name=g.name, device=device.name)

    if verify:
        pre = verify_graph(g)
        if pre:
            raise PassVerificationError("<input>", pre)
    report.modeled_before_s = _modeled_serial_s(g, device, linked=False)

    out = g
    for p in plist:
        before = out
        ctx.artifacts = {}
        t0 = time.perf_counter()
        out = p.fn(before, ctx)
        wall = time.perf_counter() - t0
        verified = False
        if verify:
            problems = verify_graph(out)
            for inv_name, pred in p.invariants:
                if not pred(out):
                    problems.append(f"declared invariant violated: {inv_name}")
            if problems:
                raise PassVerificationError(p.name, problems)
            verified = True
        summary = dict(p.summarize(before, out)) if p.summarize else {}
        summary.update(ctx.artifacts)
        report.record(PassRecord(
            name=p.name, wall_s=wall,
            nodes_before=before.num_ops(), nodes_after=out.num_ops(),
            edges_before=_edge_count(before), edges_after=_edge_count(out),
            verified=verified, summary=summary))
    report.modeled_after_s = _modeled_serial_s(out, device, linked=True)
    if key is not None:
        if len(_OPTIMIZE_CACHE) >= _OPTIMIZE_CACHE_MAX:
            _OPTIMIZE_CACHE.pop(next(iter(_OPTIMIZE_CACHE)))
        # store private copies: callers may mutate the graph or report
        # (autotune appends PassRecords) they received
        _OPTIMIZE_CACHE[key] = (out.clone(), dataclasses.replace(
            report, passes=list(report.passes)))
    return out, report


# ---------------------------------------------------------------------------
# Built-in passes (the paper's stages, registered)
# ---------------------------------------------------------------------------

def _summarize_fuse(before: Graph, after: Graph) -> dict[str, Any]:
    fused = [n for n in after.nodes if n.op_type == "cbr"]
    return {"cbr_fused": len(fused)}


def _no_fusable_chain_left(g: Graph) -> bool:
    """After fusion the §3 pattern finder must come up empty (fixpoint)."""
    from . import patterns
    return not patterns.find_cbr_fusions(g)


register_pass(Pass(
    name="fuse_cbr",
    fn=lambda g, ctx: linking.fuse_cbr(g),
    description="Preprocessing fusion: Conv+Bn(+Bias)+Relu -> CBR (paper §3)",
    invariants=(("no_fusable_chain_left", _no_fusable_chain_left),),
    summarize=_summarize_fuse,
))


def _summarize_link(before: Graph, after: Graph) -> dict[str, Any]:
    groups = linking.link_groups(after)
    linked_ops = [n for n in after.nodes if n.op_type in ("cbra", "cbrm")]
    return {"link_groups": len(groups), "linked_ops": len(linked_ops)}


register_pass(Pass(
    name="link_operators",
    fn=lambda g, ctx: linking.link(g),
    description="Vertical optimization: Table-1 operator linking (paper §4.1)",
    summarize=_summarize_link,
))


def _summarize_dos(before: Graph, after: Graph) -> dict[str, Any]:
    plans = dos.plans(after)
    split = [p for p in plans.values() if p.param_chunks]
    worst = max((p.imbalance for p in plans.values()), default=0.0)
    return {"split_plans": len(plans), "param_splits": len(split),
            "max_imbalance": round(worst, 4)}


def _all_compute_planned(g: Graph) -> bool:
    return all("split_plan" in n.dataflow for n in g.nodes
               if n.op_type in dos.COMPUTE_OPS)


register_pass(Pass(
    name="dos_split",
    fn=lambda g, ctx: dos.optimize(g, ctx.device),
    description="Horizontal optimization: DSP-aware operator split (paper §4.2)",
    invariants=(("every_compute_op_has_split_plan", _all_compute_planned),),
    summarize=_summarize_dos,
))


def _dxenos_fn(g: Graph, ctx: PassContext) -> Graph:
    """d-Xenos planning (§5): Algorithm 1 over the Figure-6 scheme set.

    Annotates every compute op with its best per-op scheme (the paper's
    winning "Ring-Mix") and records the best whole-graph scheme in the
    report.  ``options``: ``n_devices`` (default 4), ``sync`` (ring|ps),
    ``annotate`` (default True; False skips the per-op Ring-Mix search
    when only the whole-graph scheme is wanted — it costs one Algorithm-1
    run per compute op).
    """
    from . import planner  # local: planner imports linking

    n_devices = int(ctx.options.get("n_devices", 4))
    sync = ctx.options.get("sync", "ring")
    best, best_t, _ = planner.plan_distributed(g, n_devices, sync, ctx.device)
    out = g
    if ctx.options.get("annotate", True):
        mix = planner.plan_mix(g, n_devices, sync, ctx.device)
        out = g.clone()
        for node in out.nodes:
            if node.name in mix:
                node.dataflow["partition_scheme"] = str(mix[node.name])
    ctx.artifacts.update({
        "n_devices": n_devices, "sync": sync,
        "best_scheme": str(best), "best_modeled_s": best_t,
    })
    return out


register_pass(Pass(
    name="dxenos_plan",
    fn=_dxenos_fn,
    description="d-Xenos partition-scheme planning, Algorithm 1 (paper §5)",
))


#: chunk sizes the serving scheduler may choose between — a small closed set
#: so the engine's jitted chunk function compiles at most len(...) variants.
SERVE_CHUNK_SIZES: tuple[int, ...] = (8, 16, 32, 64)

#: KV block sizes the paged pool may be built with (same closed-set logic:
#: each distinct block size is a distinct compiled pool shape).
SERVE_KV_BLOCK_SIZES: tuple[int, ...] = (8, 16, 32)


def _plan_kv_pool(slots: int, max_len: int, chunk: int,
                  avg_prompt: float, shards: int = 1,
                  window: int = 0, mixed: bool = False) -> dict[str, Any]:
    """Size the paged KV pool from the prompt-length distribution.

    * ``kv_block_size`` — largest candidate dividing the horizon (the
      block table must tile it exactly — that equality is also what
      keeps the paged gather's axis layout identical to the dense ring
      buffer) that does not exceed half the average prompt: smaller
      blocks waste less to fragmentation and share shorter prefixes, a
      larger one keeps tables and gathers shallow.
    * ``kv_pool_blocks`` — without stats, the dense-equivalent capacity
      ``slots * horizon/bs`` (admission can then never be block-gated);
      with stats, requests are modeled at twice their prompt length of
      context, floored so one maximal request always fits.
    * ``shards`` — concat-TP mesh width: each shard stores ``1/shards``
      of every block's kv-head bytes, so the fragmentation target scales
      up by ``shards`` (a ``shards``-times-larger token block has the
      same per-device bytes the unsharded target aims at, and fewer,
      shallower block tables amortize the per-dispatch collectives).
    * ``window`` — sliding-window width (0 = full attention).  A ring
      pool's horizon is the *window*, not ``max_len``: every request
      holds a fixed window-sized lease whose blocks are rewritten in
      place as the window slides, so admission prices O(window) blocks
      however long the chat runs.
    * ``mixed`` — heterogeneous stack (sliding *and* global layers): the
      main geometry is the classic pool for the global layers (horizon =
      ``max_len``), plus a separate ``kv_ring_blocks`` ring capacity for
      the sliding layers; the shared block size must tile both spans.
    """
    w = min(window, max_len) if window else 0
    horizon = max_len if mixed else (w or max_len)
    fallback = False
    divisors = [b for b in SERVE_KV_BLOCK_SIZES if horizon % b == 0
                and (not mixed or w % b == 0)]
    if not divisors:
        # no preferred size tiles this horizon: fall back to the largest
        # power-of-two divisor (>=1 always exists), so planned defaults
        # never hand the engine a block size it must reject — but the
        # caller must see it happened (a 1/2/4-token block pool fragments
        # badly and shares almost no prefixes), so the fallback is
        # surfaced in the plan and the PassReport instead of silently
        # shipping a degraded geometry
        fallback = True
        divisors = [next(b for b in (4, 2, 1)
                         if horizon % b == 0 and (not mixed or w % b == 0))]
    target = avg_prompt / 2 if avg_prompt > 0 else float(chunk)
    target *= max(int(shards), 1)
    fitting = [b for b in divisors if b <= max(target, divisors[0])]
    bs = max(fitting) if fitting else divisors[0]
    per_seq = -(-horizon // bs)
    if window and not mixed:
        # ring leases are fixed at window size: prompt stats can never
        # shrink them (the window is full whenever context >= window)
        pool_blocks = slots * per_seq
    elif avg_prompt > 0:
        modeled = -(-int(min(horizon, 2 * avg_prompt)) // bs)
        pool_blocks = max(per_seq, slots * modeled)
    else:
        pool_blocks = slots * per_seq
    out = {
        "kv_block_size": bs,
        "kv_pool_blocks": pool_blocks,
        # fraction of a full-horizon dense cache's KV slots the pool does
        # not allocate — for a ring pool this is the O(window)-vs-O(seq)
        # saving the sliding family exists for
        "kv_saving": round(max(0.0, 1.0 - pool_blocks * bs
                                / (slots * max_len)), 4),
    }
    if mixed:
        out["kv_window"] = w
        out["kv_ring_blocks"] = slots * (w // bs)
    elif window:
        out["kv_window"] = horizon
    if fallback:
        out["kv_block_fallback"] = True
    return out


#: speculative draft lengths the planner may choose between (0 = off); a
#: closed set for the same reason as the chunk sizes — each (k+1)-wide
#: verify dispatch is a distinct compiled shape.
SERVE_SPEC_KS: tuple[int, ...] = (0, 2, 4, 6, 8, 12, 16)

#: modeled marginal cost of one extra verify position, in decode-step
#: units.  The verify forward is a fused scan of k+1 decode bodies, so a
#: position costs roughly one decode step's compute but amortizes its
#: dispatch; docs/serving.md states this as the verify overhead bound.
SPEC_VERIFY_OVERHEAD = 0.5


def _plan_spec_k(accept_rate: float) -> int:
    """Choose the draft length from the observed acceptance rate.

    Expected tokens committed by one verify over ``k`` drafts, when each
    draft is accepted i.i.d. with probability ``p``, is the geometric
    partial sum ``E(k) = (1 - p^(k+1)) / (1 - p)``; its cost is modeled as
    ``1 + SPEC_VERIFY_OVERHEAD * k`` decode steps (+1 for the bonus
    position).  Pick the ``k`` in :data:`SERVE_SPEC_KS` with the best
    tokens-per-step; when nothing beats plain decode (``k = 0``, score 1)
    speculation is planned **off** — low-acceptance workloads (random
    text) must not pay the draft tax.  ``accept_rate < 0`` means no drafts
    verified yet: start mid-range and let the first measured rate decide.
    """
    if accept_rate < 0:
        return 4
    p = min(max(accept_rate, 0.0), 0.999)
    best_k, best_score = 0, 1.0
    for k in SERVE_SPEC_KS:
        expected = (1.0 - p ** (k + 1)) / (1.0 - p)
        score = expected / (1.0 + SPEC_VERIFY_OVERHEAD * k)
        if score > best_score + 1e-9:
            best_k, best_score = k, score
    return best_k


def _serve_schedule_fn(g: Graph, ctx: PassContext) -> Graph:
    """Serving-schedule planning: StageTimer stats -> slot/chunk plan.

    The continuous-batching scheduler (repro.serving.scheduler) feeds its
    observed per-stage timings through this pass and executes the plan it
    gets back — the same pattern as ``dxenos_plan`` (measure, model, choose)
    applied to request-level dataflow instead of operator-level dataflow.

    ``options`` (all optional; the scheduler quantizes the floats so that
    steady-state re-planning hits the optimize() result cache):

      * ``slots``            — decode-batch width (default 4);
      * ``max_len``          — per-slot KV budget (default 256);
      * ``queue_depth``      — requests waiting at plan time;
      * ``decode_step_s``    — observed mean batched-decode step time;
      * ``prefill_token_s``  — observed mean prefill time per prompt token;
      * ``avg_prompt_len``   — observed mean admitted prompt length;
      * ``can_chunk``        — whether the model supports chunked prefill
        (attention-only families);
      * ``chunk_ratio``      — target chunk cost in decode-step units
        (default 4.0: one prefill chunk may stall decode by ~4 steps);
      * ``kv``               — ``"dense"`` (default) or ``"paged"``: paged
        engines additionally get ``kv_block_size`` / ``kv_pool_blocks``
        sized from the prompt-length distribution (see
        :func:`_plan_kv_pool`), and their prefill mode is pinned to
        ``chunked`` (a block pool has no one-shot splice path);
      * ``sliding_window`` — window width of a sliding-attention family
        (0 = full attention): the paged pool runs in ring mode and its
        geometry tiles the *window*, not ``max_len`` — admission prices
        O(window) blocks per request;
      * ``kv_mixed`` — heterogeneous (layer-pattern) stack mixing sliding
        and global layers: ``kv_growth`` reads ``"mixed"`` and a paged
        plan carries both the classic geometry (global layers, horizon =
        ``max_len``) and ``kv_ring_blocks`` (sliding layers, window-sized
        leases);
      * ``constant_state`` — the family carries recurrent (SSM/hybrid)
        state: per-request decode state is O(1) in context, surfaced as
        ``kv_growth: "constant"`` in the plan;
      * ``spec`` — ``"off"`` (default), ``"ngram"`` or ``"draft"``:
        speculative engines additionally get a planned ``spec_k`` draft
        length chosen from ``SERVE_SPEC_KS`` by the observed
        ``spec_accept_rate`` (see :func:`_plan_spec_k`; -1 = no stats yet);
      * ``mesh_shards``      — concat-TP width of the serving mesh (1 =
        unsharded): a sharded engine with no stats starts at the widest
        chunk (per-dispatch collectives amortize over chunk tokens), and
        the paged-pool geometry scales its block-size target by the shard
        count (per-shard block bytes stay constant — see
        :func:`_plan_kv_pool`).

    The plan — chunk size from ``SERVE_CHUNK_SIZES``, admission width,
    per-tick preemption bound, ``batched``-vs-``chunked`` prefill mode,
    replan period, and the paged-KV pool geometry — is annotated on every
    node (``dataflow["serve_plan"]``) and recorded in the report via
    ``ctx.artifacts``.
    """
    o = ctx.options
    slots = int(o.get("slots", 4))
    max_len = int(o.get("max_len", 256))
    queue_depth = int(o.get("queue_depth", 0))
    decode_s = float(o.get("decode_step_s", 0.0))
    prefill_tok_s = float(o.get("prefill_token_s", 0.0))
    avg_prompt = float(o.get("avg_prompt_len", 0.0))
    can_chunk = bool(o.get("can_chunk", True))
    ratio = float(o.get("chunk_ratio", 4.0))
    shards = int(o.get("mesh_shards", 1))
    window = int(o.get("sliding_window", 0))
    mixed = bool(o.get("kv_mixed", False))
    constant_state = bool(o.get("constant_state", False))

    if decode_s > 0.0 and prefill_tok_s > 0.0:
        # largest chunk whose modeled cost stays under `ratio` decode steps:
        # long prompts interleave with decode instead of stalling the batch.
        # Measured sharded timings already carry the per-dispatch collective
        # cost, so no separate mesh term is needed here.
        budget_tokens = ratio * decode_s / prefill_tok_s
        chunk = SERVE_CHUNK_SIZES[0]
        for c in SERVE_CHUNK_SIZES:
            if c <= budget_tokens:
                chunk = c
    elif shards > 1:
        # no stats on a sharded engine: start at the largest candidate —
        # every prefill-chunk dispatch pays 2*n_layers all_gathers
        # regardless of chunk width, so wider chunks amortize the
        # collective latency until measurements say otherwise
        chunk = SERVE_CHUNK_SIZES[-1]
    else:
        chunk = 32  # no stats yet: middle of the candidate set
    chunk = min(chunk, max_len)

    kv = str(o.get("kv", "dense"))

    # batched vs chunked prefill: a one-shot prefill of an average prompt
    # stalls the whole decode batch for avg_prompt * prefill_token_s.  When
    # that stall exceeds the chunk budget (`ratio` decode steps) the prompts
    # are long enough that interleaved chunked prefill wins; short prompts
    # take the lower-overhead one-shot path (chunk-granularity dispatch
    # overhead dominates them — the CPU measurement that motivated this).
    if kv == "paged":
        mode = "chunked"  # a block pool prefills chunk-by-chunk only
    elif not can_chunk:
        mode = "batched"
    elif decode_s > 0.0 and prefill_tok_s > 0.0 and avg_prompt > 0.0:
        stall_steps = avg_prompt * prefill_tok_s / decode_s
        mode = "chunked" if stall_steps > ratio else "batched"
    else:
        mode = "chunked"  # no stats yet: keep the interleaving default

    # preemption bound: every eviction re-prefills the victim's context
    # later, one chunk per tick — cap per-tick preemptions so that modeled
    # restore traffic stays within one chunk budget (`ratio` decode steps).
    if decode_s > 0.0 and prefill_tok_s > 0.0:
        restore_steps = max(chunk * prefill_tok_s / decode_s, 1e-9)
        preempt = int(min(max(slots - 1, 0), ratio / restore_steps))
    else:
        preempt = 1 if slots > 1 else 0

    plan = {
        "slots": slots,
        "chunk": chunk,
        # admission fills every free slot in one tick; under light load the
        # queue bounds it so the report shows what will actually happen
        "admit": slots if queue_depth == 0 else min(slots, queue_depth),
        "preempt": preempt,
        "prefill_mode": mode,
        # without stats the rest of this plan is a guess: replan at half
        # the requested period to re-measure sooner; with stats, keep the
        # caller's cadence (steady-state replans are cache hits anyway)
        "replan_every": int(o.get("replan_every", 32))
                        if decode_s > 0.0 and prefill_tok_s > 0.0
                        else max(1, int(o.get("replan_every", 32)) // 2),
        "modeled_chunk_cost_steps": round(chunk * prefill_tok_s / decode_s, 2)
                                    if decode_s > 0 else None,
    }
    if shards > 1:
        plan["mesh_shards"] = shards
    # how per-request KV grows with context — the dataflow shape the cache
    # family gives the serving plan: "linear" (full attention, O(seq)),
    # "window" (sliding, O(window)), "constant" (SSM/hybrid recurrent
    # state; a hybrid's sliding attention layers are window-bounded too),
    # "mixed" (layer-pattern stack: sliding layers window-bounded, global
    # layers linear — total growth is linear with a per-token slope of
    # only the global layer count)
    plan["kv_growth"] = ("constant" if constant_state
                         else "mixed" if mixed
                         else "window" if window else "linear")
    if kv == "paged":
        plan["kv"] = kv
        plan.update(_plan_kv_pool(slots, max_len, chunk, avg_prompt,
                                  shards, window, mixed))
    # the serving engine resolves a KernelPlan once (kernel_select pass)
    # and hands it back through every replan: echoing it into the serve
    # plan keeps the per-site backend choice visible in stats()/reports
    # without making replans cache-miss on it
    kplan = o.get("kernel_plan")
    if kplan:
        plan["kernel_plan"] = dict(kplan)
    spec = str(o.get("spec", "off"))
    if spec != "off":
        # speculative engines: plan the draft length from the observed
        # acceptance rate (the engine feeds it through the scheduler's
        # replan path); spec_k == 0 turns speculation off until a later
        # replan sees a better rate
        rate = float(o.get("spec_accept_rate", -1.0))
        plan["spec"] = spec
        plan["spec_k"] = _plan_spec_k(rate)
        plan["spec_accept_rate"] = rate
    out = g.clone()
    for node in out.nodes:
        node.dataflow["serve_plan"] = dict(plan)
    ctx.artifacts.update(plan)
    return out


register_pass(Pass(
    name="serve_schedule",
    fn=_serve_schedule_fn,
    description="Serving-schedule planning: stage stats -> slot/chunk/"
                "admit/preempt/prefill-mode plan for the continuous-"
                "batching scheduler",
))


# ---------------------------------------------------------------------------
# Kernel routing (kernel_select)
# ---------------------------------------------------------------------------

#: per-site backend vocabulary the router chooses from.  A backend must be
#: listed here before ``kernel_select`` may pick it and before a
#: :class:`KernelPlan` will accept it (docs/kernels.md walks through adding
#: one).  Sites are the serving hot-path dispatch points:
#:
#:   * ``decode_dense``  — dense ring-buffer decode attention
#:                         (``xla`` einsum+softmax | ``pallas`` flash-decode);
#:   * ``decode_paged``  — block-paged decode attention (``gather`` the block
#:                         table into a dense view | ``fold`` replace the K
#:                         gather with an exact one-hot contraction, bit-
#:                         identical | ``pallas`` scalar-prefetched kernel);
#:   * ``decode_ring``   — wraparound ring-paged decode attention for
#:                         sliding-window families (``gather`` only today:
#:                         gather the ring block table into a slot-ordered
#:                         dense view, then dense masked attention);
#:   * ``prefill_chunk`` — chunked prefill attention (``xla`` only today);
#:   * ``ssm_scan``      — the masked SSD state-scan of SSM/hybrid decode
#:                         and chunked prefill (``xla`` only today);
#:   * ``linked_matmul`` — the linked cbra op in the CNN engine
#:                         (``xla`` fused | ``pallas`` linked_cbr_pool);
#:   * ``sampler``       — per-request token sampling (``reference`` two-sort
#:                         | ``fused`` one-sort, fused into the decode-step
#:                         dispatch | ``pallas`` sort-free threshold kernel).
KERNEL_SITE_BACKENDS: dict[str, tuple[str, ...]] = {
    "decode_dense": ("xla", "pallas"),
    "decode_paged": ("gather", "fold", "pallas"),
    "decode_ring": ("gather",),
    "prefill_chunk": ("xla",),
    "linked_matmul": ("xla", "pallas"),
    "sampler": ("reference", "fused", "pallas"),
    "ssm_scan": ("xla",),
}


@dataclasses.dataclass(frozen=True)
class KernelPlan:
    """Per-site kernel backend choice, produced by ``kernel_select``.

    The defaults are the seed path (pure-XLA attention, gathered paged
    view, two-sort reference sampler) so ``KernelPlan()`` reproduces the
    pre-routing engine bit for bit — the serving-fuzz baseline.  Frozen
    and hashable: the serving engine keys its jit caches on
    ``(max_len, plan)``, and ``repr`` round-trips through the optimize()
    result cache's option fingerprint.
    """

    decode_dense: str = "xla"
    decode_paged: str = "gather"
    decode_ring: str = "gather"
    prefill_chunk: str = "xla"
    linked_matmul: str = "xla"
    sampler: str = "reference"
    ssm_scan: str = "xla"

    def __post_init__(self):
        for site, backend in self.items():
            allowed = KERNEL_SITE_BACKENDS[site]
            if backend not in allowed:
                raise PipelineError(
                    f"unknown backend {backend!r} for kernel site "
                    f"{site!r}; have {allowed}")

    def items(self) -> list[tuple[str, str]]:
        return [(f.name, getattr(self, f.name))
                for f in dataclasses.fields(self)]

    def as_dict(self) -> dict[str, str]:
        return dict(self.items())


def _modeled_decode_paged(o: dict[str, Any]) -> tuple[str, dict[str, Any]]:
    """Roofline the two CPU paged-decode lowerings: gather vs fold.

    ``gather`` reads K and V pool blocks through dynamic-index takes and
    materializes a dense per-request view; ``fold`` computes the K view
    as an exact one-hot contraction over the physical-block axis — a
    dense matmul XLA fuses into the decode step, eliminating the K-side
    take (V is still gathered).  Fold trades select FLOPs proportional
    to pool occupancy for dropping the K gather's scalarized indexing,
    which the model charges as a latency term on top of the copy
    traffic; the winner depends on pool geometry, and measured timings
    (``tools/kernel_tune.py``) override this model when present.

    Under a concat-TP mesh (``mesh_shards`` > 1) each device holds only
    ``K / shards`` kv heads of every block, so all per-token KV traffic —
    the quantity both lowerings are priced on — shrinks by the shard
    count; the gather's per-block take dispatches do not (every shard
    issues the same takes on its slice).
    """
    B = int(o.get("slots", 4))
    H = int(o.get("q_heads", 8))
    K = int(o.get("kv_heads", max(1, H // 4)))
    D = int(o.get("head_dim", 64))
    W = int(o.get("max_len", 256))
    bs = int(o.get("kv_block_size", 0))
    P = int(o.get("kv_pool_blocks", 0))
    shards = int(o.get("mesh_shards", 1))
    if bs <= 0 or P <= 0:
        return "gather", {}
    itemsize = 4
    K_loc = max(1, K // max(shards, 1))
    H_loc = max(1, H // max(shards, 1))
    kv_bytes = K_loc * D * itemsize
    att_flops = 4 * B * H_loc * D * W          # scores + PV, per shard
    # per-block dynamic-index dispatch overhead for one take (seconds):
    # the CPU cost the fold lowering exists to remove.
    take_s = float(o.get("gather_take_s", 2e-7))
    n_blocks = B * (W // bs)
    gather_bytes = 2 * (2 * B * W * kv_bytes)  # K+V: pool read + view write
    fold_flops = (att_flops
                  + 2 * B * W * P * K_loc * D)  # one-hot K select matmul
    fold_bytes = (P * bs * kv_bytes            # K pool, read in place
                  + 2 * B * W * kv_bytes)      # V: pool read + view write
    gather_s = (cm.roofline(att_flops, gather_bytes, 0).serial_s
                + 2 * n_blocks * take_s)       # K and V takes
    fold_s = (cm.roofline(fold_flops, fold_bytes, 0).serial_s
              + n_blocks * take_s)             # V take only
    choice = "fold" if fold_s < gather_s else "gather"
    return choice, {"decode_paged_modeled_s": {
        "gather": round(gather_s, 12), "fold": round(fold_s, 12)}}


def select_kernel_plan(options: dict[str, Any] | None = None,
                       ) -> tuple[KernelPlan, dict[str, Any]]:
    """Decide the per-site backends.  Returns ``(plan, decision detail)``.

    ``options``:

      * ``accelerator`` — ``jax.default_backend()`` of the executing
        device (default ``"cpu"``); TPUs route attention and the sampler
        to the Pallas kernels where they tile (``head_dim`` and ``vocab``
        multiples of 128; the reason for any other choice lands in
        ``pallas_untiled``), hosts keep XLA attention and take the
        one-sort ``fused`` sampler;
      * ``slots`` / ``q_heads`` / ``kv_heads`` / ``head_dim`` /
        ``max_len`` / ``kv_block_size`` / ``kv_pool_blocks`` — geometry
        for the gather-vs-fold roofline (:func:`_modeled_decode_paged`);
      * ``vocab`` — the sampler's row width;
      * ``timings`` — ``{"site:backend": seconds}`` measured by the
        micro-benchmark sweep (``launch/autotune.py`` /
        ``tools/kernel_tune.py``); a site with measured candidates takes
        the argmin and skips the heuristics entirely.
    """
    o = dict(options or {})
    acc = str(o.get("accelerator", "cpu"))
    timings = dict(o.get("timings") or {})
    tpu = acc == "tpu"
    detail: dict[str, Any] = {"accelerator": acc}

    def measured(site: str) -> str | None:
        seen = {b: float(timings[f"{site}:{b}"])
                for b in KERNEL_SITE_BACKENDS[site]
                if f"{site}:{b}" in timings}
        if not seen:
            return None
        detail[f"{site}_measured_s"] = {b: round(v, 9)
                                        for b, v in sorted(seen.items())}
        return min(seen, key=seen.get)

    paged_default, paged_detail = _modeled_decode_paged(o)
    detail.update(paged_detail)
    # a TPU takes a Pallas kernel only where it tiles the geometry; the
    # reason for every site left on XLA is recorded, never silent
    from repro.kernels import LANE
    from repro.kernels.decode_attention.decode_attention import (
        tpu_tiling_error)
    untiled = {}
    if tpu:
        err = tpu_tiling_error(int(o.get("head_dim", LANE)))
        if err:
            untiled.update(decode_dense=err, decode_paged=err)
        if int(o.get("vocab", LANE)) % LANE:
            untiled["sampler"] = f"vocab {o['vocab']} is not a multiple " \
                                 f"of {LANE}"
        if untiled:
            detail["pallas_untiled"] = untiled

    def tpu_pallas(site: str) -> bool:
        return tpu and site not in untiled

    plan = KernelPlan(
        decode_dense=measured("decode_dense")
        or ("pallas" if tpu_pallas("decode_dense") else "xla"),
        decode_paged=measured("decode_paged")
        or ("pallas" if tpu_pallas("decode_paged") else paged_default),
        decode_ring=measured("decode_ring") or "gather",
        prefill_chunk=measured("prefill_chunk") or "xla",
        linked_matmul=measured("linked_matmul")
        or ("pallas" if tpu else "xla"),
        sampler=measured("sampler")
        or ("pallas" if tpu_pallas("sampler") else "fused"),
        ssm_scan=measured("ssm_scan") or "xla",
    )
    return plan, detail


def _kernel_select_fn(g: Graph, ctx: PassContext) -> Graph:
    """Kernel-routing lowering: annotate the per-site :class:`KernelPlan`.

    The plan lands on every node (``dataflow["kernel_plan"]``) and in the
    report via ``ctx.artifacts`` — the same measure/model/choose pattern
    as ``dxenos_plan`` and ``serve_schedule``, applied to backend
    dispatch instead of partitioning or scheduling.  Options are
    documented on :func:`select_kernel_plan`.
    """
    plan, detail = select_kernel_plan(ctx.options)
    out = g.clone()
    for node in out.nodes:
        node.dataflow["kernel_plan"] = plan.as_dict()
    ctx.artifacts.update({**plan.as_dict(), **detail})
    return out


register_pass(Pass(
    name="kernel_select",
    fn=_kernel_select_fn,
    description="Kernel routing: roofline cost model + measured timings "
                "-> per-site KernelPlan (decode attention, prefill, "
                "linked matmul, sampler)",
))


#: engine mode -> pass list (the Fig.-7 ablation axes; ``ho`` is DOS without
#: the vertical rewrites, which is why it is not a numbered level)
MODE_PASSES: dict[str, tuple[str, ...]] = {
    "vanilla": (),
    "ho": ("dos_split",),
    "xenos": ("fuse_cbr", "link_operators", "dos_split"),
}


def optimize_for_mode(g: Graph, mode: str,
                      device: DeviceSpec | None = None,
                      verify: bool = True) -> tuple[Graph, PassReport]:
    """Pipeline entry keyed by engine execution mode (vanilla/ho/xenos)."""
    if mode not in MODE_PASSES:
        raise PipelineError(f"unknown engine mode {mode!r}; "
                            f"have {sorted(MODE_PASSES)}")
    return optimize(g, device, passes=MODE_PASSES[mode], verify=verify)
