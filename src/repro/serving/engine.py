"""Continuous-batching serving engine: executes the scheduler's TickPlans.

A fixed decode batch of ``slots``.  Each tick the scheduler
(``repro.serving.scheduler``) decides admissions, prefill-chunk assignments
and the decode set; the engine turns those into (at most) three batched
jitted dispatches:

  * **admit** — free slot rows are recycled (`Model.reset_cache_rows`); in
    the one-shot modes the whole admission batch is prefilled in a single
    padded multi-sequence ``prefill_step`` call;
  * **prefill_chunk** — one fixed-shape ``(slots, chunk)`` call advances
    every prefilling slot by up to ``chunk`` prompt tokens *in the same tick
    decode runs*, so long prompts interleave with decoding instead of
    stalling the batch;
  * **decode** — all DECODE slots step together (``serve_step``) with a
    ``live`` mask keeping bystander rows' caches untouched.

Logits become tokens through one batched sampling dispatch
(``repro.serving.sampling``): every slot applies its *own* request's
:class:`SamplingParams` (temperature / top-k / top-p, per-request PRNG
seed) with keys derived only from that request's seed and emitted-token
count — so sampled output is independent of slot assignment and batch
composition, and ``greedy`` is simply the temperature-0 default policy.

Every hot-path dispatch routes through a :class:`KernelPlan`
(``core.pipeline``): by default the ``kernel_select`` pass picks a backend
per site (decode attention dense/paged, sampler, ...) from the roofline
cost model and any measured timings; under a fused-sampler plan the
decode step and the sampler compile into a *single* jitted dispatch
(``serve_sample``), token-identical to the reference path.  Pass
``kernel_plan="off"`` for the seed path or an explicit plan to pin one.

The KV caches are the engine's state; every dispatch updates slot rows in
place, so retire/refill never copies surviving requests.  With
``kv="paged"`` the dense per-slot rows are replaced by a block pool
(``repro.serving.kv_pool``): per-request block tables, refcounted
shared-prefix blocks (admission probes a prefix cache and skips
already-cached prefill chunks), and admission gated on free blocks.  The
dense path remains the differential-testing oracle — the randomized
serving-equivalence harness (``tests/test_serving_fuzz.py``) keeps the two
bit-identical under greedy and seeded sampling.

The engine shares the optimization pipeline's stage instrumentation
(``repro.core.pipeline.StageTimer``): every stage of a tick is timed on
the host clock, and ``stats()`` returns the same structured per-stage
record the pass manager emits plus the scheduler's current serve_schedule
plan.  Each stage is also a ``serving.``-prefixed span (``serving.step``,
``serving.plan``, ``serving.admit``, ``serving.prefill_chunk``,
``serving.decode``, ``serving.verify``, ``serving.replan``; the model
stages split into ``.inputs``, ``.dispatch``, ``.wait`` and ``.emit``) in
any active ``jax.profiler`` session, on the device trace's clock.  A
request's ``queued_s`` is its time waiting for a slot, and a paged
engine's ``stats()["kv_pool"]`` sums the blocks leased and the blocks
written over ticks (``docs/serving.md``, "Tracing").
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.pipeline import KernelPlan, StageTimer
from repro.kernels.fused_sampler.ops import fused_sample, fused_sample_grid
from repro.models import cache_family as CF

from .kv_pool import KVBlockPool, MixedKVPool, PoolConfig
from .sampling import SamplingParams, sample_token_grid, sample_tokens
from .scheduler import (RequestState, Scheduler, SchedulerConfig, TickPlan,
                        serve_plan_graph)
from .speculative import (SPEC_OFF, DraftModelProposer, NGramProposer,
                          SpecParams, SpecStats)


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray                 # (prompt_len,) int32
    max_new_tokens: int = 16
    #: per-request generation policy; None = the engine's default
    sampling: SamplingParams | None = None
    #: higher admits first and may preempt strictly-lower DECODE slots
    priority: int = 0
    #: per-request speculative-decoding policy; None = the engine's default
    spec: SpecParams | None = None
    generated: list = dataclasses.field(default_factory=list)
    done: bool = False
    #: seconds spent waiting for a slot, summed over every admission (a
    #: preempted request waits again); None until first admitted
    queued_s: float | None = None


def settle_ticks(prompt_len: int, chunk: int) -> int:
    """Ticks for a fresh admission wave to clear chunked prefill and settle
    into decode.  Drivers that inject late high-priority work wait this
    long first — preemption only means anything once the batch is
    decoding (up-front submission would merely sort the queue)."""
    return 2 * max(1, -(-prompt_len // max(chunk, 1))) + 1


def _serving_jits(model, max_len: int, plan: KernelPlan, mesh=None,
                  caches=None) -> dict:
    """Jitted serving steps, cached **on the model**: every engine over the
    same model shares one compiled prefill/chunk/decode/reset/sample, so
    spinning up an engine (benchmarks do it per policy) never recompiles.
    Keyed on ``(max_len, plan)`` — a :class:`KernelPlan` is frozen and
    hashable, and every dispatch below routes through it.  With a >1-shard
    ``mesh`` the hot-path entries (serve / chunk / serve_sample / verify)
    are shard_map-wrapped under the concat-TP partition specs
    (``repro.distributed.tp``) — ``caches`` supplies the layout the specs
    are built from, and the cache key gains ``(mesh, layout)`` so dense
    and paged sharded engines never share a wrapper.  The metadata-only
    entries (reset / rollback) stay plain jit: they touch no K/V payload
    math and GSPMD propagates the input shardings through them.

    The plan's ``sampler`` site picks the sampling lowering:

      * ``"reference"`` — the seed path: two-sort ``sample_tokens`` in its
        own dispatch after decode;
      * ``"fused"`` / ``"pallas"`` — the fused-sampler kernel package
        (one-sort jnp / Pallas threshold kernel), plus a ``serve_sample``
        entry that fuses decode and sampling into a *single* jitted
        dispatch — the per-tick dispatch overhead, not the sort FLOPs, is
        what dominates sampling cost at serving vocab sizes.
    """
    from repro.distributed import tp as _tp

    cache = getattr(model, "_serving_jit_cache", None)
    if cache is None:
        cache = {}
        model._serving_jit_cache = cache
    shards = _tp.serving_mesh_shards(mesh)
    key = (max_len, plan) if shards <= 1 else \
        (max_len, plan, mesh, type(caches.kv).__name__)
    if key not in cache:
        vocab = model.cfg.vocab
        ax = _tp.SERVING_AXIS if shards > 1 else None
        if shards > 1:
            from jax.sharding import PartitionSpec as _P
            pspecs = _tp.serving_param_specs(model.param_specs())
            cspecs = _tp.serving_cache_specs(caches)

            def wrap(f, n_rep_args):
                # every non-param/cache operand (tokens, masks, sampling
                # policy arrays) and every logits/token output is
                # replicated; check_vma off — unchecked-replication out
                # specs are exactly what concat-TP produces (each shard
                # computes the identical full-width result)
                return jax.jit(jax.shard_map(
                    f, mesh=mesh,
                    in_specs=(pspecs, cspecs) + (_P(),) * n_rep_args,
                    out_specs=(_P(), cspecs), check_vma=False))
        else:
            wrap = lambda f, n_rep_args: jax.jit(f)
        if plan.sampler == "reference":
            sample = jax.jit(functools.partial(sample_tokens, vocab=vocab))
            sample_grid = jax.jit(
                functools.partial(sample_token_grid, vocab=vocab))
            serve_sample = None
        else:
            backend = "pallas" if plan.sampler == "pallas" else "jnp"
            sample = functools.partial(fused_sample, vocab=vocab,
                                       backend=backend)
            sample_grid = functools.partial(fused_sample_grid, vocab=vocab,
                                            backend=backend)

            def serve_sample_body(p, c, t, live, seeds, steps, temps, ks,
                                  ps):
                logits, new_c = model.serve_step(p, c, t, live=live,
                                                 plan=plan, shard_axis=ax)
                toks = fused_sample(logits, seeds, steps, temps, ks, ps,
                                    vocab=vocab, backend=backend)
                return toks, new_c

            serve_sample = wrap(serve_sample_body, 7)
        if shards > 1:
            # the logits come back replicated; each shard samples its own
            # copy (a Pallas kernel cannot be partitioned automatically)
            sample, sample_grid = (
                jax.jit(jax.shard_map(f, mesh=mesh, in_specs=(_P(),) * 6,
                                      out_specs=_P(), check_vma=False))
                for f in (sample, sample_grid))

        cache[key] = {
            "serve": wrap(
                lambda p, c, t, live: model.serve_step(
                    p, c, t, live=live, plan=plan, shard_axis=ax), 2),
            "prefill": jax.jit(
                lambda p, b: model.prefill_step(p, b, max_len=max_len)),
            "chunk": wrap(
                lambda p, c, t, off, nn: model.prefill_chunk(
                    p, c, t, off, nn, shard_axis=ax), 3),
            "reset": jax.jit(
                lambda c, rows: model.reset_cache_rows(c, rows)),
            "sample": sample,
            "serve_sample": serve_sample,
            # speculative decoding (jax.jit re-traces per distinct verify
            # width K1, bounded by the closed spec-k candidate set)
            "verify": wrap(
                lambda p, c, t, nn: model.verify_step(
                    p, c, t, nn, plan=plan, shard_axis=ax), 2),
            "rollback": jax.jit(
                lambda c, keep, rows: model.rollback_cache_rows(
                    c, keep, rows)),
            "sample_grid": sample_grid,
        }
    return cache[key]


class ServingEngine:
    def __init__(self, model, params, *, slots: int = 4, max_len: int = 256,
                 eos_id: int = -1, greedy: bool = True,
                 sampling: SamplingParams | None = None,
                 prefill_mode: str | None = None, chunk: int = 32,
                 replan_every: int = 32, kv: str = "dense",
                 kv_block_size: int | None = None,
                 kv_pool_blocks: int | None = None,
                 spec: SpecParams | None = None, spec_k_max: int = 16,
                 draft_model=None, draft_params=None,
                 kernel_plan: KernelPlan | str | None = None,
                 kernel_timings: dict | None = None, mesh=None,
                 device=None):
        if kv not in ("dense", "paged"):
            raise ValueError(f"unknown kv mode {kv!r}; have dense|paged")
        if device is not None and mesh is not None:
            raise ValueError("an engine lives on one device or on a mesh, "
                             "not both")
        from repro.distributed import tp as _tp
        self.model = model
        self.params = params
        #: concat-TP serving mesh (repro.distributed.tp); validated here so
        #: an incompatible config fails at construction, not mid-serve
        self.mesh = mesh
        self.mesh_shards = _tp.validate_serving_tp(model.cfg, mesh)
        #: the one device holding this engine's params and caches (a
        #: router replica's chip); None = JAX's default device
        self.device = device
        self.slots = slots
        self.max_len = max_len
        self.eos_id = eos_id
        self.greedy = greedy
        self.kv = kv
        self.pool: KVBlockPool | MixedKVPool | None = None
        #: ring-window width (tokens) when the paged pool runs in ring
        #: mode — admission prices this, not the decode horizon
        self._kv_window = 0
        #: speculative policy for requests that carry no SpecParams of
        #: their own; SPEC_OFF = plain one-token-per-tick decode.
        self.default_spec = spec if spec is not None else SPEC_OFF
        self._spec_k_max = int(spec_k_max)
        self.spec_stats = SpecStats()
        self._ngram = NGramProposer()
        self._draft: DraftModelProposer | None = None
        if draft_model is not None:
            self._draft = DraftModelProposer(
                draft_model, draft_params, slots=slots, max_len=max_len)
        if self.default_spec.mode == "draft" and self._draft is None:
            raise ValueError(
                "spec mode 'draft' needs a draft_model (a reduced config "
                "from repro.configs — see ModelConfig.reduced())")
        if self.default_spec.mode != "off":
            self._check_spec_model(model.cfg)
        #: policy for requests that carry no SamplingParams of their own:
        #: ``greedy=True`` is argmax (temperature 0); ``greedy=False``
        #: samples the raw softmax (temperature 1).
        if sampling is None:
            sampling = SamplingParams() if greedy \
                else SamplingParams(temperature=1.0)
        self.default_sampling = sampling
        self.timer = StageTimer(prefix="serving.")
        self.tokens_out = 0        # every generated token (prefill + decode)
        self._decode_tokens = 0    # decode-loop tokens only (throughput)
        self._prefill_tokens = 0   # prompt tokens pushed through prefill

        cfg = model.cfg
        if self.mesh_shards > 1 and any(f.ssm
                                        for f in CF.layer_cache_families(cfg)):
            raise ValueError(
                "mesh-sharded serving does not support constant-state "
                f"(SSM/hybrid) families ({CF.family_label(cfg)}): the "
                "concat-TP partition specs cover attention KV only")
        if self.mesh_shards > 1 and getattr(cfg, "layer_pattern", ""):
            # the shard_map cache specs (and the jit-cache key) assume one
            # stacked homogeneous cache layout; per-layer tuples are not
            # threaded through the concat-TP path
            raise ValueError(
                "mesh-sharded serving does not support heterogeneous "
                f"(layer_pattern={cfg.layer_pattern!r}) cache stacks")
        auto_mode = prefill_mode is None
        if auto_mode:
            prefill_mode = ("chunked" if CF.supports_chunked_prefill(cfg)
                            else "batched")
        if self.mesh_shards > 1 and prefill_mode != "chunked":
            # the one-shot prefill_step path is not shard-threaded (it
            # splices whole cache rows host-side); every sharded dispatch
            # goes through the chunked entries
            raise ValueError(
                f"a mesh-sharded engine requires prefill_mode='chunked', "
                f"not {prefill_mode!r}")
        if kv == "paged":
            # paged KV rides on chunked prefill (a block pool has no
            # one-shot row-splice path) and needs pageable attention state:
            # all-full layers take the paged pool, all-sliding layers the
            # wraparound ring; constant-state (SSM/hybrid) layers hold no
            # pageable KV and stay dense
            if not CF.supports_paged(cfg):
                raise ValueError(
                    f"kv='paged' needs an attention KV family, not "
                    f"{CF.family_label(cfg)} (constant-state layers hold "
                    "no pageable KV)")
            if prefill_mode != "chunked":
                raise ValueError(
                    f"kv='paged' requires prefill_mode='chunked', "
                    f"not {prefill_mode!r}")
        if prefill_mode == "chunked" and not CF.supports_chunked_prefill(cfg):
            raise ValueError(f"{cfg.family} cannot run chunked prefill; "
                             f"use prefill_mode='batched'")
        self.scheduler = Scheduler(
            SchedulerConfig(slots=slots, max_len=max_len, chunk=chunk,
                            prefill_mode=prefill_mode,
                            replan_every=replan_every),
            plan_graph=serve_plan_graph(
                cfg.name, slots, cfg.d_model, cfg.d_ff or cfg.d_model,
                cfg.vocab))
        self.scheduler.eos_id = None if eos_id < 0 else eos_id
        self.scheduler.chunk_supported = CF.supports_chunked_prefill(cfg)
        # dataflow-shape facts the serve_schedule pass prices: a sliding
        # window bounds per-request KV, recurrent state doesn't grow at
        # all, a mixed stack grows per layer kind.  Derived from the
        # per-layer descriptors, NOT the raw cfg.sliding_window field — a
        # family whose layers ignore the field (pure SSM with
        # sliding_window set) must not make the planner price a phantom
        # window.
        plan_window = CF.kv_plan_window(cfg)
        if plan_window:
            self.scheduler.kv_window = min(plan_window, max_len)
        self.scheduler.kv_mixed = CF.family_label(cfg) == "mixed"
        self.scheduler.constant_state = any(
            f.ssm for f in CF.layer_cache_families(cfg))
        # replans feed the observed acceptance rate through serve_schedule
        # and adopt its planned spec_k (requests with k=None use it)
        self.scheduler.spec_mode = self.default_spec.mode
        # a pinned mode stays pinned; auto engines let serve_schedule
        # switch batched<->chunked from observed stats (never paged ones:
        # the pool cannot execute a one-shot batched prefill; nor sharded
        # ones: the one-shot path is not shard-threaded)
        self.scheduler.adopt_prefill_mode = (auto_mode and kv != "paged"
                                             and self.mesh_shards == 1)
        # replans price the per-dispatch collective cost of a sharded plan
        self.scheduler.mesh_shards = self.mesh_shards

        if kv == "paged":
            self._init_paged_kv(kv_block_size, kv_pool_blocks)
        else:
            self.caches = model.init_caches(slots, max_len)
        # seed the pre-replan plan with the KV growth class so stats() is
        # honest before the first serve_schedule pass runs (same
        # derivation the pass itself uses)
        self.scheduler.last_plan["kv_growth"] = (
            "constant" if self.scheduler.constant_state
            else "mixed" if self.scheduler.kv_mixed
            else "window" if self.scheduler.kv_window else "linear")
        self._kernel_report = None  # PassReport when the plan was routed
        self.kernel_plan = self._resolve_kernel_plan(kernel_plan,
                                                     kernel_timings)
        self.scheduler.kernel_plan = self.kernel_plan.as_dict()
        self._last_tokens = jnp.zeros((slots, 1), jnp.int32)
        if device is not None:
            # every dispatch follows its committed params and caches, so
            # the whole engine runs on this device
            self.params = jax.device_put(self.params, device)
            self.caches = jax.device_put(self.caches, device)
            self._last_tokens = jax.device_put(self._last_tokens, device)
            if self._draft is not None:
                self._draft.params = jax.device_put(self._draft.params,
                                                    device)
                self._draft.caches = jax.device_put(self._draft.caches,
                                                    device)
        if self.mesh_shards > 1:
            # place params/caches under their concat-TP shardings once —
            # otherwise every dispatch would re-shard the replicated
            # arrays; subsequent cache updates come back from the
            # shard_mapped entries already laid out
            from jax.sharding import NamedSharding
            from jax.sharding import PartitionSpec as _P

            def place(tree, specs):
                return jax.tree.map(
                    lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
                    tree, specs, is_leaf=lambda x: isinstance(x, _P))

            self.params = place(self.params,
                                _tp.serving_param_specs(model.param_specs()))
            self.caches = place(self.caches,
                                _tp.serving_cache_specs(self.caches))
        jits = _serving_jits(model, max_len, self.kernel_plan, mesh=mesh,
                             caches=self.caches)
        self._serve = jits["serve"]
        self._prefill = jits["prefill"]
        self._chunk_step = jits["chunk"]
        self._reset_rows = jits["reset"]
        self._sample_step = jits["sample"]
        self._serve_sample = jits["serve_sample"]
        self._verify = jits["verify"]
        self._rollback = jits["rollback"]
        self._sample_grid_step = jits["sample_grid"]

    def _resolve_kernel_plan(self, kernel_plan, timings) -> KernelPlan:
        """Resolve the engine's per-site kernel routing.

        ``None`` (the default) runs the ``kernel_select`` pass over the
        scheduler's proxy graph — the roofline model plus any measured
        timings (``tools/kernel_tune.py``) pick a backend per site, and
        the decision lands in a PassReport (``stats()["kernel_report"]``).
        ``"off"`` pins the seed path (``KernelPlan()``); an explicit
        :class:`KernelPlan` is honored as given.
        """
        if kernel_plan == "off":
            return KernelPlan()
        if kernel_plan is not None:
            if not isinstance(kernel_plan, KernelPlan):
                raise ValueError(
                    f"kernel_plan must be a KernelPlan, 'off' or None, "
                    f"got {kernel_plan!r}")
            return kernel_plan
        from repro.core import pipeline
        cfg = self.model.cfg
        options = {
            "accelerator": jax.default_backend(),
            "slots": self.slots, "max_len": self.max_len,
            "q_heads": cfg.n_heads, "kv_heads": cfg.n_kv_heads,
            "head_dim": cfg.resolved_head_dim, "vocab": cfg.vocab,
        }
        if self.mesh_shards > 1:
            options["mesh_shards"] = self.mesh_shards
        if self.pool is not None:
            options["kv_block_size"] = self.pool.cfg.block_size
            options["kv_pool_blocks"] = self.pool.cfg.pool_blocks
        if timings:
            options["timings"] = dict(sorted(timings.items()))
        _, report = pipeline.optimize(self.scheduler.plan_graph,
                                      passes=("kernel_select",),
                                      options=options)
        self._kernel_report = report
        summary = report.passes[-1].summary
        return KernelPlan(**{site: summary[site]
                             for site in KernelPlan().as_dict()})

    @staticmethod
    def _check_spec_model(cfg, rid: int | None = None) -> None:
        """Speculative decoding rewinds the KV cache by position, which
        only a full-attention family supports (recurrent state cannot be
        rolled back; a sliding-window ring has already freed the blocks a
        rollback would rewind into).  With ``rid`` the error names the
        offending request — the per-request ``submit()`` path, so a
        spec-carrying request on a sliding/SSM engine fails loudly at
        submission instead of being caught only at engine construction."""
        if not CF.supports_spec(cfg):
            who = f"request {rid}: " if rid is not None else ""
            raise ValueError(
                f"{who}speculative decoding needs a full-attention family, "
                f"not {cfg.family}"
                + (" with a sliding window" if cfg.sliding_window else "")
                + " (rollback across an evicted window block or recurrent "
                "state is undefined)")

    # -- paged KV -------------------------------------------------------------
    def _init_paged_kv(self, block_size: int | None,
                       pool_blocks: int | None) -> None:
        """Build the block pool.  Unset geometry comes from the
        ``serve_schedule`` pass (the same planner the scheduler replans
        through), which sizes ``block_size``/``pool_blocks`` from slots,
        the KV horizon and — once stats exist — the prompt-length
        distribution.

        A sliding-window family runs the pool in **ring** mode
        (``CF.paged_kind``): every slot's block table tiles the *window*,
        not the decode horizon, writes wrap in place, and admission is
        priced against window-sized leases — long-chat KV is O(window)
        instead of O(seq).  A heterogeneous (layer-pattern) stack runs
        **mixed**: a :class:`MixedKVPool` leases a classic table for the
        global layers and a ring table for the sliding layers per request,
        so long-chat KV is O(window) on the sliding layers and O(seq) only
        on the global ones."""
        cfg = self.model.cfg
        kind = CF.paged_kind(cfg)
        window = 0
        if kind in ("ring", "mixed"):
            window = min(CF.kv_plan_window(cfg), self.max_len)
            if self.scheduler.cfg.chunk > window:
                raise ValueError(
                    f"{kind} paged KV needs chunk "
                    f"({self.scheduler.cfg.chunk}) <= window ({window}): a "
                    "larger chunk would write the same ring slot twice in "
                    "one scatter")
        # the token span one slot's *classic* block table must tile: the
        # window in ring mode, the full decode horizon otherwise (mixed
        # keeps the full horizon on its global layers; its ring table is
        # sized separately below)
        horizon = self.max_len if kind == "mixed" else (window or
                                                        self.max_len)
        if block_size is None or pool_blocks is None:
            from repro.core import pipeline
            options = {"slots": self.slots, "max_len": self.max_len,
                       "kv": "paged", "can_chunk": True,
                       "replan_every": self.scheduler.cfg.replan_every}
            if window:
                options["sliding_window"] = window
            if kind == "mixed":
                options["kv_mixed"] = True
            if self.mesh_shards > 1:
                options["mesh_shards"] = self.mesh_shards
            _, report = pipeline.optimize(
                self.scheduler.plan_graph,
                passes=("serve_schedule",), options=options)
            plan = report.passes[-1].summary
            if block_size is None:
                # clamp the planned block to the configured prefill chunk:
                # a block larger than the chunk could never fill in one
                # chunk, pushing prefix-cache hits out by a whole chunk
                block_size = int(plan["kv_block_size"])
                fitting = [b for b in pipeline.SERVE_KV_BLOCK_SIZES
                           if horizon % b == 0
                           and (not window or window % b == 0)
                           and b <= max(self.scheduler.cfg.chunk, 8)]
                if fitting:
                    block_size = min(block_size, max(fitting))
            if pool_blocks is None:
                # size capacity from the *final* block size (construction
                # has no prompt stats, so the planned capacity is always
                # the dense-equivalent token budget) — taking the planner's
                # count verbatim would over-allocate whenever the caller's
                # block size differs from the planned one
                pool_blocks = self.slots * (horizon // block_size)
        if horizon % block_size:
            what = f"window {horizon}" if window and kind != "mixed" \
                else f"max_len {self.max_len}"
            raise ValueError(
                f"{what} is not a multiple of the KV block size "
                f"{block_size}: the block table must tile it exactly "
                "(this is also what keeps paged and dense decode "
                "bit-identical)")
        if kind == "mixed" and window % block_size:
            raise ValueError(
                f"window {window} is not a multiple of the KV block size "
                f"{block_size}: the ring block table must tile it exactly")
        max_blocks = horizon // block_size
        self._kv_window = window
        if kind == "mixed":
            ring_max = window // block_size
            ring_blocks = self.slots * ring_max
            self.pool = MixedKVPool(
                PoolConfig(block_size=block_size, pool_blocks=pool_blocks,
                           max_blocks_per_seq=max_blocks),
                PoolConfig(block_size=block_size, pool_blocks=ring_blocks,
                           max_blocks_per_seq=ring_max),
                window)
            self.caches = self.model.init_paged_caches(
                self.slots, pool_blocks=pool_blocks, block_size=block_size,
                max_blocks=max_blocks, ring_pool_blocks=ring_blocks,
                ring_max_blocks=ring_max)
        else:
            self.pool = KVBlockPool(PoolConfig(
                block_size=block_size, pool_blocks=pool_blocks,
                max_blocks_per_seq=max_blocks, shards=self.mesh_shards))
            self.caches = self.model.init_paged_caches(
                self.slots, pool_blocks=pool_blocks, block_size=block_size,
                max_blocks=max_blocks)
        self.scheduler.kv_mode = "paged"
        self.scheduler.kv_window = window
        self.scheduler.kv_gate = self._kv_gate
        self.scheduler.on_admit = self._kv_on_admit
        self.scheduler.on_release = self._kv_on_release

    def _kv_horizon(self, sreq) -> int:
        """Context length the request may reach in this slot: its prefill
        context plus the decode budget it still holds."""
        remaining = max(sreq.req.max_new_tokens - len(sreq.req.generated), 0)
        return min(sreq.prompt_len + remaining, self.max_len)

    def _kv_gate(self, sreq, victim=None) -> bool:
        """Admission gate: are there enough allocatable blocks (counting a
        preemption victim's, when one is about to be evicted)?"""
        ok = self.pool.can_admit(
            sreq.prompt_tokens, self._kv_horizon(sreq),
            victim_rid=victim.req.rid if victim is not None else None,
            window=self._kv_window)
        if not ok:
            self.pool.gated_rids.add(sreq.req.rid)
        return ok

    def _kv_on_admit(self, sreq) -> None:
        """Lease blocks and probe the prefix cache: ``cached`` tokens are
        already present in shared blocks, so the prefill starts there —
        those chunks are never dispatched at all."""
        _, cached = self.pool.allocate(sreq.req.rid, sreq.prompt_tokens,
                                       self._kv_horizon(sreq),
                                       window=self._kv_window)
        sreq.pos = cached

    def _kv_on_release(self, sreq) -> None:
        if self.pool.holds(sreq.req.rid):  # zero-budget retires never leased
            self.pool.free(sreq.req.rid)

    # -- public API -----------------------------------------------------------
    def submit(self, req: Request) -> None:
        rspec = req.spec if req.spec is not None else self.default_spec
        if rspec.mode != "off":
            self._check_spec_model(self.model.cfg, rid=req.rid)
            if rspec.mode == "draft" and self._draft is None:
                raise ValueError(
                    f"request {req.rid} wants spec mode 'draft' but the "
                    "engine holds no draft model")
            if self.pool is None \
                    and len(req.prompt) + req.max_new_tokens > self.max_len:
                # rollback rewinds the dense ring by absolute position,
                # which a wrapped ring has overwritten — a speculative
                # request must fit the horizon (the paged pool enforces
                # the same bound below for every request)
                raise ValueError(
                    f"request {req.rid}: prompt ({len(req.prompt)}) + "
                    f"max_new_tokens ({req.max_new_tokens}) exceeds the "
                    f"{self.max_len}-token horizon; a speculative request "
                    "cannot wrap the dense KV ring (its rollback rewinds "
                    "by position)")
        if self.pool is not None \
                and len(req.prompt) + req.max_new_tokens > self.max_len:
            # the paged horizon is exact: a context past max_len has no
            # block to land in (the dense ring wraps instead — garbage,
            # but its long-standing behaviour).  Enforcing prompt+max_new
            # here also keeps a preemption restore's folded context
            # (prompt + generated, plus the remaining budget) inside the
            # horizon for every later re-admission.
            raise ValueError(
                f"request {req.rid}: prompt ({len(req.prompt)}) + "
                f"max_new_tokens ({req.max_new_tokens}) exceeds the "
                f"{self.max_len}-token KV horizon of the paged pool")
        sreq = self.scheduler.submit(req)
        if req.sampling is None and not self.default_sampling.greedy:
            # a non-greedy default must not make every request replay one
            # PRNG stream: derive a per-request stream from the submission
            # index (stable across batch layouts, unlike slot or tick)
            req.sampling = dataclasses.replace(
                self.default_sampling,
                seed=self.default_sampling.seed + sreq.seq)

    def step(self) -> int:
        """One engine tick: execute the scheduler's plan.  Returns the
        number of slots that produced a token this tick."""
        with self.timer.stage("step"):
            with self.timer.stage("plan"):
                plan = self.scheduler.plan_tick()
            produced = 0
            if plan.admissions:
                with self.timer.stage("admit"):
                    self._admit(plan)
                if self.scheduler.cfg.prefill_mode != "chunked":
                    produced += len(plan.admissions)
            if plan.prefill:
                with self.timer.stage("prefill_chunk"):
                    produced += self._prefill_chunks(plan)
            if plan.decode_slots:
                drafts = self._plan_drafts(plan)
                if drafts:
                    with self.timer.stage("verify"):
                        produced += self._decode_verify(plan, drafts)
                else:
                    # no slot drafted this tick: the plain one-token decode
                    # dispatch, exactly as a spec=off engine would run it
                    with self.timer.stage("decode"):
                        produced += self._decode(plan)
            if self.pool is not None:
                self._tally_kv_use()
            self._maybe_replan()
        return produced

    def run(self, max_steps: int = 10_000) -> None:
        steps = 0
        while self.scheduler.pending() and steps < max_steps:
            self.step()
            steps += 1

    def decode_logits(self, plan: KernelPlan | None = None) -> jax.Array:
        """``(slots, vocab)`` logits the next decode step would give every
        slot, under this engine's kernel plan or ``plan``, advancing
        nothing: the probe that compares kernel plans, KV layouts and
        meshes on one engine state.  Rows of slots not decoding are
        computed but meaningless."""
        serve = _serving_jits(self.model, self.max_len,
                              plan or self.kernel_plan, mesh=self.mesh,
                              caches=self.caches)["serve"]
        live = np.asarray([s is not None and s.state == RequestState.DECODE
                           for s in self.scheduler.active])
        logits, _ = serve(self.params, self.caches, self._last_tokens,
                          jnp.asarray(live))
        return logits[:, :self.model.cfg.vocab]

    # -- admission ------------------------------------------------------------
    def _admit(self, plan: TickPlan) -> None:
        if self.scheduler.cfg.prefill_mode == "chunked":
            if self.pool is not None:
                if type(self.caches) is tuple:
                    # layer-pattern stack: per-layer tables — under a
                    # MixedKVPool the classic lease row goes on the
                    # global layers and the ring lease row on the
                    # sliding layers (a ring cache is the one carrying
                    # per-slot positions); a homogeneous pattern shares
                    # the single pool's table across every layer
                    mixed_pool = isinstance(self.pool, MixedKVPool)
                    new_caches = list(self.caches)
                    for i, cache in enumerate(new_caches):
                        kv = cache.kv
                        ring = hasattr(kv, "positions")
                        bt, ln = kv.block_tables, kv.length
                        for sreq in plan.admissions:
                            rid = sreq.req.rid
                            row = jnp.asarray(
                                self.pool.ring_block_table(rid)
                                if ring and mixed_pool
                                else self.pool.block_table(rid))
                            bt = bt.at[sreq.slot].set(row)
                            ln = ln.at[sreq.slot].set(sreq.pos)
                        kv = kv._replace(block_tables=bt, length=ln)
                        if ring:
                            pos = kv.positions
                            for sreq in plan.admissions:
                                pos = pos.at[sreq.slot].set(-1)
                            kv = kv._replace(positions=pos)
                        new_caches[i] = cache._replace(kv=kv)
                    self.caches = tuple(new_caches)
                    return
                # paged: point the admitted slots' block tables at their
                # freshly leased blocks; length starts at the prefix-cache
                # hit (those positions are already in shared blocks)
                kv = self.caches.kv
                bt, ln = kv.block_tables, kv.length
                for sreq in plan.admissions:
                    row = jnp.asarray(self.pool.block_table(sreq.req.rid))
                    bt = bt.at[:, sreq.slot].set(row)
                    ln = ln.at[:, sreq.slot].set(sreq.pos)
                kv = kv._replace(block_tables=bt, length=ln)
                if hasattr(kv, "positions"):
                    # ring mode: a recycled slot may hold the previous
                    # occupant's per-slot positions — clear them so the
                    # attention validity mask (positions >= 0) starts empty
                    pos = kv.positions
                    for sreq in plan.admissions:
                        pos = pos.at[:, sreq.slot].set(-1)
                    kv = kv._replace(positions=pos)
                self.caches = self.caches._replace(kv=kv)
                return
            # dense: recycle the admitted rows so the first chunk sees an
            # empty ring buffer; one-shot modes skip this — their splice
            # below overwrites every cache leaf of those rows anyway
            rows = np.zeros((self.slots,), bool)
            for sreq in plan.admissions:
                rows[sreq.slot] = True
            self.caches = self._reset_rows(self.caches, jnp.asarray(rows))
            return  # prefill happens chunk by chunk from the next plan on

        # one-shot modes: batched padded prefill of the whole admission set.
        # Recurrent families can't mask a padded tail out of their state
        # scan, so they batch equal-length groups instead of padding.
        paddable = self.model.cfg.attention_only
        if self.scheduler.cfg.prefill_mode == "serial" or \
                (len(plan.admissions) == 1):
            groups = [[s] for s in plan.admissions]
        elif paddable:
            groups = [list(plan.admissions)]
        else:
            by_len: dict[int, list] = {}
            for s in plan.admissions:
                by_len.setdefault(s.prompt_len, []).append(s)
            groups = list(by_len.values())
        for group in groups:
            self._prefill_group(group, padded=paddable and len(group) > 1)

    def _prefill_group(self, group, padded: bool) -> None:
        lens = [s.prompt_len for s in group]
        S = max(lens)
        toks = np.zeros((len(group), S), np.int32)
        for i, s in enumerate(group):
            toks[i, :lens[i]] = s.prompt_tokens
        batch = {"tokens": jnp.asarray(toks)}
        if padded:
            batch["lengths"] = jnp.asarray(lens, jnp.int32)
        logits, fresh = self._prefill(self.params, batch)
        with self.timer.stage(".wait"):
            jax.block_until_ready(logits)
        slots_arr = jnp.asarray([s.slot for s in group], jnp.int32)
        # splice the freshly prefilled rows into their slots' cache rows;
        # heterogeneous tuples' leaves are batch-major (no layer axis)
        splice = (lambda full, one: full.at[slots_arr].set(one)) \
            if type(self.caches) is tuple \
            else (lambda full, one: full.at[:, slots_arr].set(one))
        self.caches = jax.tree.map(splice, self.caches, fresh)
        toks_out = self._sample(logits, group)
        for i, sreq in enumerate(group):
            t = int(toks_out[i])
            self._last_tokens = self._last_tokens.at[sreq.slot, 0].set(t)
            self._prefill_tokens += lens[i]
            self.tokens_out += 1  # first token comes out of the prefill
            self.scheduler.note_admitted_prefilled(sreq, t)

    # -- chunked prefill ------------------------------------------------------
    def _prefill_chunks(self, plan: TickPlan) -> int:
        C = self.scheduler.cfg.chunk
        with self.timer.stage(".inputs"):
            toks = np.zeros((self.slots, C), np.int32)
            offsets = np.zeros((self.slots,), np.int32)
            n_new = np.zeros((self.slots,), np.int32)
            rows: list = [None] * self.slots
            for a in plan.prefill:
                toks[a.slot, :a.n_new] = \
                    a.sreq.prompt_tokens[a.start:a.start + a.n_new]
                offsets[a.slot] = a.start
                n_new[a.slot] = a.n_new
                rows[a.slot] = a.sreq
        with self.timer.stage(".dispatch"):
            logits, self.caches = self._chunk_step(
                self.params, self.caches, jnp.asarray(toks),
                jnp.asarray(offsets), jnp.asarray(n_new))
        if any(a.start + a.n_new >= a.sreq.prompt_len for a in plan.prefill):
            toks_out = self._sample(logits, rows)
        else:
            # no slot finishes its prompt this tick: the logits are dead,
            # skip the sampling dispatch (but still sync for stage timing)
            toks_out = None
            with self.timer.stage(".wait"):
                jax.block_until_ready(logits)
        produced = 0
        with self.timer.stage(".emit"):
            for a in plan.prefill:
                self._prefill_tokens += a.n_new
                done = a.start + a.n_new >= a.sreq.prompt_len
                first = int(toks_out[a.slot]) if done else None
                if self.pool is not None:
                    # register freshly *full* prefill blocks in the prefix
                    # cache (before note_prefilled: its _emit may retire
                    # the request and release the lease in the same call)
                    self.pool.note_prefilled(a.sreq.req.rid,
                                             a.start + a.n_new)
                if done:
                    self._last_tokens = \
                        self._last_tokens.at[a.slot, 0].set(first)
                    self.tokens_out += 1
                    produced += 1
                self.scheduler.note_prefilled(a.sreq, a.n_new, first)
        return produced

    # -- speculative decode ---------------------------------------------------
    def _resolve_spec(self, sreq) -> tuple[SpecParams, int]:
        """A request's effective spec policy and draft length: its own
        SpecParams (or the engine default); ``k=None`` takes the
        serve_schedule-planned ``spec_k`` (mid-range 4 before any plan)."""
        sp = sreq.req.spec if sreq.req.spec is not None else self.default_spec
        if sp.mode == "off":
            return sp, 0
        k = sp.k
        if k is None:
            k = self.scheduler.cfg.spec_k
            if k is None:
                k = 4
        return sp, min(int(k), self._spec_k_max)

    def _plan_drafts(self, plan: TickPlan) -> dict[int, np.ndarray]:
        """Propose draft tokens per decode slot.  Empty dict = nobody
        drafted, the tick falls through to the plain decode path.

        The per-row draft length is clamped so a verify can never
        over-commit or over-write: at most ``remaining - 1`` drafts (the
        verify's bonus token then lands exactly on the budget) and at most
        ``max_len - 1 - L`` (every write stays inside the horizon — the
        dense ring must not wrap, the paged lease covers exactly the
        horizon)."""
        out: dict[int, np.ndarray] = {}
        draft_rows: list[tuple[int, int, np.ndarray, int]] = []
        for slot in plan.decode_slots:
            sreq = self.scheduler.active[slot]
            sp, k = self._resolve_spec(sreq)
            if k <= 0:
                continue
            req = sreq.req
            remaining = req.max_new_tokens - len(req.generated)
            cache_len = len(req.prompt) + len(req.generated) - 1
            k = min(k, remaining - 1, self.max_len - 1 - cache_len)
            if k <= 0:
                continue
            context = np.concatenate(
                [np.asarray(req.prompt, np.int64),
                 np.asarray(req.generated, np.int64)])
            if sp.mode == "ngram":
                d = self._ngram.propose(context, k, sp)
                if len(d):
                    out[slot] = d
            else:
                draft_rows.append((slot, req.rid, context, k))
        if draft_rows:
            for slot, d in self._draft.propose(draft_rows).items():
                if len(d):
                    out[slot] = d
        return out

    def _decode_verify(self, plan: TickPlan, drafts: dict[int, np.ndarray]
                       ) -> int:
        """One verify dispatch for the whole decode set: each drafting row
        scores ``[pending, d_1..d_k]`` in one fused forward, non-drafting
        rows ride along with one position.  Commit the longest prefix
        whose drafts match the target's keyed samples (the Leviathan rule
        for point-mass drafts — see ``repro.serving.speculative``), plus
        the bonus token at the first mismatch; rejected suffix writes roll
        back, so the caches end bit-identical to a plain decode history."""
        B = self.slots
        K1 = 1 + max(len(d) for d in drafts.values())
        toks = np.zeros((B, K1), np.int32)
        n_new = np.zeros((B,), np.int32)
        rows: list = [None] * B
        pre_len = np.zeros((B,), np.int64)
        with self.timer.stage(".wait"):
            last = np.asarray(self._last_tokens)[:, 0]
        for slot in plan.decode_slots:
            sreq = self.scheduler.active[slot]
            rows[slot] = sreq
            d = drafts.get(slot)
            toks[slot, 0] = last[slot]
            if d is not None:
                toks[slot, 1:1 + len(d)] = d
            n_new[slot] = 1 + (len(d) if d is not None else 0)
            # context tokens cached before this tick: prompt + emitted - 1
            # (the newest emitted token is still pending, never written)
            pre_len[slot] = (len(sreq.req.prompt)
                             + len(sreq.req.generated) - 1)
        logits, self.caches = self._verify(
            self.params, self.caches, jnp.asarray(toks), jnp.asarray(n_new))
        targets = self._sample(logits, rows, self._sample_grid_step)
        self.spec_stats.verify_calls += 1
        self.spec_stats.verify_positions += int(n_new.sum())

        produced = 0
        keep_len = np.zeros((B,), np.int32)
        rollback = np.zeros((B,), bool)
        for slot in plan.decode_slots:
            sreq = rows[slot]
            d = drafts.get(slot, np.zeros((0,), np.int32))
            n = 1 + len(d)
            commits = 0
            for i in range(n):
                t = int(targets[slot, i])
                self.tokens_out += 1
                self._decode_tokens += 1
                self._last_tokens = self._last_tokens.at[slot, 0].set(t)
                self.scheduler.note_decoded(slot, t)
                commits += 1
                produced += 1
                if sreq.req.done:
                    break           # EOS/budget retired mid-commit
                if i < len(d) and int(d[i]) != t:
                    break           # first rejected draft: t is the bonus
            self.spec_stats.drafts_proposed += len(d)
            self.spec_stats.drafts_accepted += commits - 1
            self.spec_stats.spec_tokens += commits
            if commits < n:
                keep_len[slot] = pre_len[slot] + commits
                rollback[slot] = True
        if rollback.any():
            self.caches = self._rollback(
                self.caches, jnp.asarray(keep_len), jnp.asarray(rollback))
        if self.pool is not None:
            self._spec_truncate_leases(plan, rows)
        return produced

    def _spec_truncate_leases(self, plan: TickPlan, rows: list) -> None:
        """Paged rollback, pool side: a decoding request can never need
        blocks past ``prompt + max_new - 1`` context tokens (the last
        emitted token is never fed back), so strandable tail blocks of
        the lease go back to the pool and the device block-table row
        forgets them."""
        kv = self.caches.kv
        bt = kv.block_tables
        changed = False
        for slot in plan.decode_slots:
            sreq = rows[slot]
            rid = sreq.req.rid
            if sreq.req.done or not self.pool.holds(rid):
                continue
            needed = len(sreq.req.prompt) + sreq.req.max_new_tokens - 1
            if self.pool.truncate(rid, needed):
                bt = bt.at[:, slot].set(
                    jnp.asarray(self.pool.block_table(rid)))
                changed = True
        if changed:
            self.caches = self.caches._replace(
                kv=kv._replace(block_tables=bt))

    # -- decode ---------------------------------------------------------------
    def _decode(self, plan: TickPlan) -> int:
        fused = self._serve_sample is not None
        with self.timer.stage(".inputs"):
            live = np.zeros((self.slots,), bool)
            rows: list = [None] * self.slots
            for slot in plan.decode_slots:
                live[slot] = True
                rows[slot] = self.scheduler.active[slot]
            if fused:
                seeds, steps, temps, ks, ps = self._sampling_arrays(rows)
        if fused:
            # fused-sampler plan: decode + sampling in ONE jitted dispatch
            # (the fused sampler's draw handles temperature-0 rows as
            # argmax internally, so greedy needs no separate shortcut)
            with self.timer.stage(".dispatch"):
                toks, self.caches = self._serve_sample(
                    self.params, self.caches, self._last_tokens,
                    jnp.asarray(live), jnp.asarray(seeds),
                    jnp.asarray(steps), jnp.asarray(temps), jnp.asarray(ks),
                    jnp.asarray(ps))
            with self.timer.stage(".wait"):
                toks = np.asarray(jax.block_until_ready(toks))
        else:
            with self.timer.stage(".dispatch"):
                logits, self.caches = self._serve(self.params, self.caches,
                                                  self._last_tokens,
                                                  jnp.asarray(live))
            toks = self._sample(logits, rows)
        with self.timer.stage(".emit"):
            for slot in plan.decode_slots:
                t = int(toks[slot])
                self.tokens_out += 1
                self._decode_tokens += 1
                self._last_tokens = self._last_tokens.at[slot, 0].set(t)
                self.scheduler.note_decoded(slot, t)
        return len(plan.decode_slots)

    # -- sampling -------------------------------------------------------------
    def _sampling_arrays(self, rows):
        """Per-slot sampling policy arrays for one batched dispatch.
        ``rows`` aligns each batch row with its ScheduledRequest (None =
        bystander row, sampled under the default policy and discarded).
        Each row's key depends only on its request's seed and
        emitted-token count, so results don't change with slot assignment
        or batch composition."""
        B = len(rows)
        seeds = np.zeros((B,), np.uint32)
        steps = np.zeros((B,), np.int32)
        temps = np.zeros((B,), np.float32)
        ks = np.zeros((B,), np.int32)
        ps = np.ones((B,), np.float32)
        for i, sreq in enumerate(rows):
            if sreq is None:
                continue
            sp = sreq.req.sampling or self.default_sampling
            seeds[i] = np.uint32(sp.seed & 0xFFFFFFFF)
            steps[i] = len(sreq.req.generated)
            temps[i] = sp.temperature
            ks[i] = sp.top_k
            ps[i] = sp.top_p
        return seeds, steps, temps, ks, ps

    def _sample(self, logits: jax.Array, rows, step=None) -> np.ndarray:
        """One batched sampling dispatch over ``(B, V)`` logits (the
        prefill paths, and decode under the reference-sampler plan), timed
        as children of the open stage.  A verify tick passes
        ``step=self._sample_grid_step`` for ``(B, K1, V)`` logits: position
        ``i`` of row ``b`` uses key ``(seed_b, emitted_b + i)`` — the same
        keys the plain decode path would use emitting those tokens one
        tick at a time (``sample_token_grid``), which is what makes
        speculative sampled streams identical, not merely equal in
        distribution."""
        with self.timer.stage(".inputs"):
            seeds, steps, temps, ks, ps = self._sampling_arrays(rows)
        with self.timer.stage(".dispatch"):
            if not temps.any():
                # all-greedy batch: plain argmax, skip the sort/cumsum
                # sampler
                toks = jnp.argmax(logits[..., :self.model.cfg.vocab],
                                  axis=-1).astype(jnp.int32)
            else:
                toks = (step or self._sample_step)(
                    logits, jnp.asarray(seeds), jnp.asarray(steps),
                    jnp.asarray(temps), jnp.asarray(ks), jnp.asarray(ps))
        with self.timer.stage(".wait"):
            return np.asarray(jax.block_until_ready(toks))

    # -- re-planning / stats --------------------------------------------------
    def _tally_kv_use(self) -> None:
        """Add this tick's KV reservation use to the pool's totals: each
        live request's context written so far (a decoding row's newest
        token is not written until the next step)."""
        written = {}
        for s in self.scheduler.active:
            if s is not None:
                written[s.req.rid] = (
                    s.pos if s.state is RequestState.PREFILL
                    else len(s.req.prompt) + len(s.req.generated) - 1)
        self.pool.tally_use(written)

    def _maybe_replan(self) -> None:
        if not self.scheduler.replan_due():
            return
        with self.timer.stage("replan"):
            # verify dispatches are the spec engine's decode steps: fold
            # them in so a mostly-speculative workload still produces
            # decode stats
            totals, counts = self.timer.totals, self.timer.counts
            decode = totals.get("decode", 0.0) + totals.get("verify", 0.0)
            decode_calls = counts.get("decode", 0) + counts.get("verify", 0)
            prefill_s = (totals.get("prefill_chunk", 0.0)
                         + totals.get("admit", 0.0))
            accept = None
            if self.default_spec.mode != "off" \
                    and self.spec_stats.drafts_proposed:
                accept = self.spec_stats.accept_rate
            self.scheduler.maybe_replan(
                decode_step_s=decode / decode_calls if decode_calls else 0.0,
                prefill_token_s=prefill_s / self._prefill_tokens
                if self._prefill_tokens else 0.0,
                accept_rate=accept)

    def stats(self) -> dict:
        """Per-stage timing + throughput + the scheduler's plan,
        pipeline-report style."""
        out = {"stages": self.timer.as_dict(), "tokens_out": self.tokens_out,
               "prefill_tokens": self._prefill_tokens,
               "plan": dict(self.scheduler.last_plan),
               "scheduler": self.scheduler.state_counts(),
               "prefill_mode": self.scheduler.cfg.prefill_mode,
               "kv": self.kv,
               "kernel_plan": self.kernel_plan.as_dict()}
        if self._kernel_report is not None:
            out["kernel_report"] = self._kernel_report.as_dict()
        if self.mesh_shards > 1:
            out["mesh_shards"] = self.mesh_shards
        if self.device is not None:
            out["device"] = str(self.device)
        if self.pool is not None:
            out["kv_pool"] = self.pool.stats()
            out["prefill_tokens_saved"] = self.pool.tokens_saved
            if self._kv_window:
                out["kv_window"] = self._kv_window
            if self.mesh_shards > 1:
                # per-device geometry: block allocation is replicated (one
                # host-side pool decides for every shard) but each shard
                # stores only its kv-head slice of every block
                cfg = self.model.cfg
                k_loc = cfg.n_kv_heads // self.mesh_shards
                itemsize = jnp.dtype(self.caches.kv.k.dtype).itemsize
                blk = self.pool.cfg.block_size
                out["kv_pool"]["per_shard"] = {
                    "kv_heads": k_loc,
                    "block_bytes": 2 * blk * k_loc
                    * cfg.resolved_head_dim * itemsize,
                    "pool_bytes": 2 * self.pool.cfg.pool_blocks * blk
                    * k_loc * cfg.resolved_head_dim * itemsize,
                }
        rep = self.scheduler.last_report
        if rep is not None:
            out["plan_report"] = rep.as_dict()
            out["plan_cache_hit"] = rep.cache_hit
        if self.default_spec.mode != "off":
            out["spec"] = {"mode": self.default_spec.mode,
                           "k": self._resolve_spec_k_for_stats(),
                           **self.spec_stats.as_dict()}
        # decode throughput counts *committed* tokens only over the decode
        # + verify wall time — draft positions the verify scored but the
        # target rejected are never emissions (see launch/serve.py)
        decode_s = sum(out["stages"].get(s, {"total_s": 0.0})["total_s"]
                       for s in ("decode", "verify"))
        if decode_s > 0:
            out["decode_tokens_per_s"] = self._decode_tokens / decode_s
        return out

    def _resolve_spec_k_for_stats(self) -> int | None:
        """The draft length currently in effect for default-spec requests
        (the planned value once serve_schedule has produced one)."""
        if self.default_spec.mode == "off":
            return None
        k = self.default_spec.k
        if k is None:
            k = self.scheduler.cfg.spec_k
            if k is None:
                k = 4
        return min(int(k), self._spec_k_max)
