"""Layer-style facade over a functional model core.

One implementation of the paddle-shaped plumbing (parameters /
state_dict / train-eval / tape-recorded forward) shared by GPTModel,
BertModel and ViTModel: the functional params become tape Parameters and
forward dispatches the whole core as ONE differentiable op.

Closure hygiene matters here: dispatch caches the op closure globally
(framework/dispatch.py _JIT_CACHE keyed by op name + qualname + static
args), so nothing passed to apply() may capture the model instance or
the call's input tensors — only the param-name tuple, the input count,
and the (small, immutable) config travel in the closure.
"""
from __future__ import annotations


def _plan_pp(plan) -> int:
    """The plan's pipeline degree (1 when absent/3D)."""
    try:
        return int(plan.axes.get("pp", 1))
    except AttributeError:
        return 1


def resolve_plan_step(step_fn, cfg=None, mesh=None, plan=None,
                      with_stats=False, overlap=None, **step_kw):
    """ONE seam turning (step_fn, plan) into the callable the jit wraps.

    pp=1 (or no plan): `functools.partial(step_fn, cfg=..., **kw)` —
    exactly the historical behavior. pp>1: the family train step cannot
    run as-is (its layer scan is on-chip; the stacked axis is now
    stage-chunked over the 'pp' mesh axis), so the resolved fn is
    parallel.pipeline_train.make_pp_step_fn's full-manual pipelined
    step honoring the same (params, opt, batch) -> (loss, new_params,
    new_opt) contract, with the optimizer kwargs (lr, betas, ...)
    forwarded to the shared apply_adamw. Wrappers that already resolved
    (the resilient guard, the telemetry instrumenter) mark their
    closure `_plan_resolved` so make_train_step never double-resolves.

    `overlap` (None = follow `plan.overlap`) selects the latency-hiding
    collective schedule (docs/parallel_training.md §Collective overlap).
    It reaches make_pp_step_fn on the pp>1 path (the per-layer ZeRO-3
    gather prefetch) and is deliberately STRIPPED on the pp=1 path —
    the family train steps don't take it; there the knob lives in the
    _ShardedTrainStep's compiler options instead."""
    import functools
    if (_plan_pp(plan) > 1
            and not getattr(step_fn, "_plan_resolved", False)):
        if mesh is None:
            raise ValueError("a pp>1 plan needs mesh= (build it with "
                             "plan.build_mesh())")
        from ..parallel.pipeline_train import make_pp_step_fn
        fn = make_pp_step_fn(cfg, plan, mesh, with_stats=with_stats,
                             overlap=overlap, **step_kw)
        fn._plan_resolved = True
        return fn
    if cfg is not None:
        step_kw = dict(step_kw, cfg=cfg)
    return functools.partial(step_fn, **step_kw) if step_kw else step_fn


def plan_step_cell(step_fn, cfg=None, mesh=None, plan=None, **step_kw):
    """The mutable inner-resolution cell wrappers (the resilient guard,
    the telemetry instrumenter) build over resolve_plan_step: returns
    `(inner, outer, make_rebuild)` where `inner(params, opt, batch)`
    dispatches to the CURRENT resolved step, `outer` is a one-slot dict
    the wrapper must fill (`outer["fn"] = <its jit-facing closure>`),
    and `make_rebuild()` is the `_plan_rebuild` hook for
    `_ShardedTrainStep.rebuild`: it re-resolves the inner against a
    degraded mesh/plan and returns a FRESH outer-forwarding wrapper —
    fresh-identity is load-bearing, because jax's tracing cache keys on
    function identity and re-jitting the same wrapper object would
    silently reuse the old mesh's trace (its shard_map eqn bakes the
    mesh in). ONE home so the subtlety cannot drift between wrappers."""
    cell = {"fn": resolve_plan_step(step_fn, cfg=cfg, mesh=mesh,
                                    plan=plan, **step_kw)}
    outer = {}

    def inner(*a, **k):
        return cell["fn"](*a, **k)

    def _plan_rebuild(new_mesh, new_plan):
        cell["fn"] = resolve_plan_step(step_fn, cfg=cfg, mesh=new_mesh,
                                       plan=new_plan, **step_kw)

        def refreshed(*a, **k):
            return outer["fn"](*a, **k)
        refreshed._plan_resolved = True
        refreshed._plan_rebuild = _plan_rebuild
        return refreshed

    return inner, outer, _plan_rebuild


def make_train_step(step_fn, cfg=None, donate=True, extra_donate=(),
                    mesh=None, plan=None, overlap=None, **step_kw):
    """jit the stacked-params functional train step with the params and
    optimizer-state buffers DONATED — step_fn(params, opt_state, batch,
    ...) -> (loss, new_params, new_opt_state) consumes both trees and
    returns same-shaped replacements, so XLA aliases the output buffers
    onto the inputs instead of holding two copies of the model + Adam
    moments live across the update (the same donate_argnums=(2, 4)
    pattern optimizer.Optimizer._build_step_fn_for already uses).

    ONE home for the pattern: bench.py, the sweep/ablation tools and the
    examples all jitted `functools.partial(train_step, cfg=cfg, ...)`
    with hand-rolled donation; they now build their step here so the
    donation (and any future jit policy) cannot drift per caller.
    `parallel.resilience.make_resilient_step` layers the fault-tolerance
    guard (non-finite skip-step + rollback/watchdog plumbing) over this
    same builder — use it instead when the loop must survive NaNs, hung
    dispatch, or restarts (docs/fault_tolerance.md). `extra_donate`
    names additional positional arg indices to donate — the telemetry
    accumulator (profiler/telemetry.py) rides through the step donated
    exactly like the params/opt buffers.

    3D auto-parallel (docs/parallel_training.md): with `mesh` (a
    build_mesh Mesh) and `plan` (parallel.planner.plan_train's
    TrainPlan) the step compiles as ONE GSPMD computation with its
    in/out shardings PINNED: params, grads-as-moments and both Adam
    moment trees land per the plan's remapped PARAM_SPECS (shape-aware
    degrade to replicated per leaf), the batch shards over the plan's
    dp×fsdp axes, everything else replicates. Pinning is the serving
    engine's `_pin_cache` discipline applied to the train state —
    out sharding == in sharding per leaf, so the donated buffers alias
    exactly and propagation heuristics cannot shift layouts (or force
    a recompile) between calls. The pins derive from the FIRST call's
    shapes; subsequent calls reuse the one compiled executable (the
    `trace_count` property observes this — the zero-recompiles-after-
    warmup test gate).

    `overlap=None` follows the plan's own `overlap` field (TrainPlan /
    Plan, default off); an explicit bool wins. On: the pp>1 pipelined
    step double-buffers its per-layer ZeRO-3 weight gathers
    (parallel/pipeline_train.py), and the GSPMD step asks XLA for
    async-collective fusion / collective-matmul on TPU-class backends
    (_ShardedTrainStep — a no-op on CPU, where the xla_tpu_* flags
    don't exist). docs/parallel_training.md §Collective overlap."""
    import jax
    from ..profiler import RecordEvent, monitor
    if overlap is None:
        overlap = bool(getattr(plan, "overlap", False))
    donate_argnums = ((0, 1) + tuple(extra_donate)) if donate else ()
    with RecordEvent("facade.make_train_step"):
        monitor.counter("facade_train_step_builds").add()
        if (mesh is not None and _plan_pp(plan) > 1
                and not getattr(step_fn, "_plan_resolved", False)):
            # 4D plan on a raw family step: swap in the full-manual
            # pipelined step (parallel/pipeline_train.py) with the
            # schedule-stats tail; _PipelineTrainStep strips it and
            # publishes train.bubble_fraction. Already-resolved
            # wrappers (resilient guard, telemetry) take the plain
            # _ShardedTrainStep branch below — their extra args/outputs
            # pin replicated exactly like the 3D case. The re-resolve
            # on mesh change rides the SAME _plan_rebuild hook the
            # wrappers use (_ShardedTrainStep.rebuild — one mechanism):
            # each resolution wraps in a fresh closure carrying the
            # hook, so a pp->pp1->pp degrade chain keeps re-resolving.
            def _resolve(new_mesh, new_plan):
                inner = resolve_plan_step(step_fn, cfg=cfg,
                                          mesh=new_mesh, plan=new_plan,
                                          with_stats=True,
                                          overlap=overlap, **step_kw)

                def stepfn(params, opt_state, batch, *rest):
                    return inner(params, opt_state, batch, *rest)
                stepfn._plan_resolved = True
                stepfn._plan_rebuild = _resolve
                return stepfn
            step = _PipelineTrainStep(
                _resolve(mesh, plan), mesh, plan,
                donate_argnums=donate_argnums, overlap=overlap)
            step._cfg = cfg        # oom_forensics' ledger input
            return step
        fn = resolve_plan_step(step_fn, cfg=cfg, mesh=mesh, plan=plan,
                               overlap=overlap, **step_kw)
        if mesh is None:
            return jax.jit(fn, donate_argnums=donate_argnums)
        step = _ShardedTrainStep(fn, mesh, plan,
                                 donate_argnums=donate_argnums,
                                 overlap=overlap)
        step._cfg = cfg            # oom_forensics' ledger input
        return step


class _ShardedTrainStep:
    """The planner-driven GSPMD train step: a jit whose in/out shardings
    are pinned from (plan, first-call shapes) — see make_train_step.

    Pin rules (the facade step contract `(params, opt_state, batch,
    *rest) -> (loss, new_params, new_opt, *extras)`):
    - every params/opt leaf pins by its LEAF NAME through the plan's
      remapped spec table (Adam's m/v mirror the param tree leaf for
      leaf, so the same name-keyed lookup shards the moments like
      their params; unknown names — e.g. the opt 'step' scalar —
      replicate), shape-aware per parallel.mesh.sharding_for;
    - batch leaves shard their leading dim over the plan's dp×fsdp
      axes (degrading to replicated when the dim doesn't divide);
    - all other args (poison scalars, the telemetry accumulator) and
      all non-params/opt outputs replicate, so extra_donate aliases
      stay exact (replicated in == replicated out).
    Outputs index 1/2 reuse the INPUT pins verbatim — donation aliasing
    by construction, executables that cannot drift."""

    # The latency-hiding compiler profile (docs/parallel_training.md
    # §Collective overlap): ask XLA:TPU to (a) fuse collectives into
    # async start/done pairs and slide compute between them, and (b)
    # lower every sharded einsum as a windowed collective-matmul
    # (threshold 0 MiB) so the ZeRO-3 all-gather / tp reduce-scatter
    # overlap their consuming/producing matmuls. TPU-only: CPU/GPU XLA
    # rejects unknown xla_tpu_* flags, so _build attaches these only
    # when the mesh's devices are TPU-class.
    _OVERLAP_COMPILER_OPTIONS = {
        "xla_tpu_enable_async_collective_fusion": "true",
        "xla_tpu_enable_async_collective_fusion_fuse_all_gather":
            "true",
        "xla_tpu_enable_async_collective_fusion_multiple_steps":
            "true",
        "xla_tpu_overlap_compute_collective_tc": "true",
        "xla_jf_spmd_threshold_for_windowed_einsum_mib": "0",
    }

    def __init__(self, fn, mesh, plan, donate_argnums=(),
                 overlap=False):
        self._fn = fn
        self.mesh = mesh
        self.plan = plan
        self.overlap = bool(overlap)
        self._donate = tuple(donate_argnums)
        self._jit = None
        self.in_pins = None
        self.out_pins = None

    def _compiler_options(self):
        """The overlap XLA flags, or None when they don't apply (knob
        off, or a non-TPU backend that would reject them). Numerics
        note: windowed einsum re-orders partial-sum accumulation, so
        overlap-on parity vs overlap-off is trajectory-level (<=2e-4,
        the test_plan4d convention) on real TPU; on CPU the options
        never attach and the two steps are bit-identical."""
        if not self.overlap:
            return None
        try:
            platforms = {d.platform for d in self.mesh.devices.flat}
        except AttributeError:
            return None
        if platforms != {"tpu"}:
            return None
        return dict(self._OVERLAP_COMPILER_OPTIONS)

    def _traced_fn(self):
        """The jit target: the step fn traced with this plan's mesh
        ambient, so the model-internal activation hints
        (models/gpt._sp_constraint / _tp_constraint — mesh_constraint
        reads parallel.mesh.get_mesh() at trace time) engage instead of
        degrading to identity. Without the ambient mesh GSPMD guesses
        every activation layout from the weight shardings alone — the
        audited involuntary reshards around the scan carry
        (profiler/hlo_audit findings). Identity-stable per (_fn, mesh):
        rebuilt only by rebuild(), so jax's trace cache never sees two
        names for one step."""
        from ..parallel.mesh import use_mesh
        fn, mesh = self._fn, self.mesh

        def traced(*args):
            with use_mesh(mesh):
                return fn(*args)
        return traced

    @staticmethod
    def _leaf_name(path):
        # ONE home: parallel.mesh.leaf_path_name — the manual pp step's
        # shard_map specs resolve by the same rule, and pins/specs must
        # agree leaf for leaf
        from ..parallel.mesh import leaf_path_name
        return leaf_path_name(path)

    def _state_pins(self, tree):
        """Name-keyed spec lookup, shape-aware (params AND opt trees)."""
        import jax.tree_util as jtu
        from jax.sharding import PartitionSpec as P
        from ..parallel.mesh import sharding_for
        specs = (self.plan.specs if self.plan is not None
                 and self.plan.specs else {})

        def pin(path, leaf):
            spec = specs.get(self._leaf_name(path), P())
            return sharding_for(spec, self.mesh,
                                shape=getattr(leaf, "shape", ()))
        return jtu.tree_map_with_path(pin, tree)

    def _batch_pins(self, tree):
        import jax
        from jax.sharding import PartitionSpec as P
        from ..parallel.mesh import sharding_for

        def pin(leaf):
            shape = getattr(leaf, "shape", ())
            spec = (self.plan.batch_spec(len(shape))
                    if self.plan is not None and len(shape) else P())
            return sharding_for(spec, self.mesh, shape=shape)
        return jax.tree_util.tree_map(pin, tree)

    def _replicated_pins(self, tree):
        import jax
        from jax.sharding import PartitionSpec as P
        from ..parallel.mesh import sharding_for
        rep = sharding_for(P(), self.mesh)
        return jax.tree_util.tree_map(lambda _: rep, tree)

    def shard_args(self, params, opt_state, batch, *rest):
        """device_put the step arguments onto their pins (host trees or
        arrays laid out for another mesh land on this plan's layout —
        the Resharder move, paid once at setup/first call)."""
        import jax
        pins = (self._state_pins(params), self._state_pins(opt_state),
                self._batch_pins(batch),
                *(self._replicated_pins(r) for r in rest))
        return tuple(jax.device_put(a, p)
                     for a, p in zip((params, opt_state, batch) + rest,
                                     pins))

    def _build(self, args):
        import jax
        in_pins = (self._state_pins(args[0]), self._state_pins(args[1]),
                   self._batch_pins(args[2]),
                   *(self._replicated_pins(a) for a in args[3:]))
        fn = self._traced_fn()
        out_struct = jax.eval_shape(fn, *args)
        if not (isinstance(out_struct, (tuple, list))
                and len(out_struct) >= 3):
            raise TypeError(
                "sharded make_train_step needs the facade step contract "
                "(loss, new_params, new_opt, ...); got output structure "
                f"{jax.tree_util.tree_structure(out_struct)}")
        out_pins = []
        for i, sub in enumerate(out_struct):
            if i == 1:
                out_pins.append(in_pins[0])       # new params == params
            elif i == 2:
                out_pins.append(in_pins[1])       # new opt == opt
            else:
                out_pins.append(self._replicated_pins(sub))
        self.in_pins, self.out_pins = in_pins, tuple(out_pins)
        jit_kw = {}
        opts = self._compiler_options()
        if opts is not None:
            jit_kw["compiler_options"] = opts
        self._jit = jax.jit(fn, in_shardings=in_pins,
                            out_shardings=self.out_pins,
                            donate_argnums=self._donate, **jit_kw)

    def __call__(self, params, opt_state, batch, *rest):
        import jax
        args = (params, opt_state, batch) + rest
        if self._jit is None:
            # first call = build + GSPMD compile + run: time it and
            # publish train.compile.* (docs/observability.md) so a
            # run's telemetry stream records what warmup cost next to
            # the trace_count zero-recompile observable (and the
            # hlo_audit's train.compile.audit_ms)
            import time
            from ..profiler import monitor
            t0 = time.perf_counter()
            self._build(args)
            args = self.shard_args(*args)
            out = self._dispatch(args)
            monitor.gauge("train.compile.wall_ms").set(
                round((time.perf_counter() - t0) * 1e3, 3))
            monitor.counter("train.compile.executables").add()
            return out
        else:
            # steady state: params/opt arrive as the previous call's
            # pinned outputs; the batch (and any scalar extras like the
            # guard's poison) come fresh from host each step. Committing
            # them here keeps the jit cache key IDENTICAL to the warmup
            # call's (committed+pinned across the board) — one
            # executable, ever (a no-op alias when the caller already
            # placed them).
            args = (params, opt_state,
                    jax.device_put(batch, self._batch_pins(batch)),
                    *(jax.device_put(r, self._replicated_pins(r))
                      for r in rest))
        return self._dispatch(args)

    def _dispatch(self, args):
        """The one executable-dispatch seam: a RESOURCE_EXHAUSTED (real
        backend OOM) dumps an oom_forensics flight black box — the
        plan's train_memory_ledger plus a live-array census — before
        re-raising, so the abort names its tenants instead of dying
        with a bare allocator message (docs/observability.md §Memory
        observability)."""
        try:
            return self._jit(*args)
        except Exception as e:                     # noqa: BLE001
            if "RESOURCE_EXHAUSTED" in str(e):
                self._dump_oom_forensics(e, args)
            raise

    def _dump_oom_forensics(self, exc, args) -> None:
        # best-effort: forensics must never mask the original failure
        try:
            from ..profiler import flight_recorder, monitor
            from ..profiler.mem_audit import live_array_census
            ledger = None
            cfg = getattr(self, "_cfg", None)
            try:
                if cfg is not None and self.plan is not None:
                    from ..cost_model import train_memory_ledger
                    batch = args[2]
                    ledger = train_memory_ledger(
                        cfg, self.plan, global_batch=batch.shape[0],
                        seq=max(int(batch.shape[1]) - 1, 1))
            except Exception:                      # noqa: BLE001
                pass
            census = live_array_census()
            monitor.counter("train.oom_forensics").add()
            rec = flight_recorder.recorder()
            rec.configure(oom_forensics={
                "where": "train_step", "error": repr(exc)[:500],
                "ledger": ledger,
                "census": census["rows"],
                "live_bytes": census["total_bytes"],
                "plan": getattr(self.plan, "name", repr(self.plan))})
            rec.note(oom_forensics="train_step")
            rec.dump("oom_forensics")
        except Exception:                          # noqa: BLE001
            pass

    def rebuild(self, mesh=None, plan=None) -> "_ShardedTrainStep":
        """Re-target this step at a new mesh/plan — the elastic replan
        seam (parallel/elastic.py: device loss shrinks the world, the
        planner degrades the plan, and the SAME step object re-pins).
        Drops the compiled executable and both pin tables; the next
        call re-derives in/out shardings from the new plan's specs and
        compiles ONE fresh executable. Because the retarget swaps in a
        brand-new `jax.jit` object (rather than feeding new shardings
        to the old one), the old mesh's executable cannot linger as a
        second cache entry — the cache key space never bifurcates, and
        `trace_count` restarts at 0 so the zero-recompiles-after-
        replan-warmup gate reads exactly like first warmup."""
        if mesh is not None:
            self.mesh = mesh
        if plan is not None:
            self.plan = plan
        self._jit = None
        self.in_pins = None
        self.out_pins = None
        # wrapped steps that bake plan internals into their closure
        # (the resilient guard / telemetry instrumenter over a pp>1
        # pipelined inner — parallel/pipeline_train.py) expose a
        # re-resolution hook; 3D closures are mesh-agnostic and carry
        # none. The hook returns a FRESH callable: jax's jaxpr-tracing
        # cache keys on function identity, so re-jitting the SAME
        # wrapper object would silently reuse the old trace with the
        # old mesh baked into its shard_map eqn.
        hook = getattr(self._fn, "_plan_rebuild", None)
        if hook is not None:
            fresh = hook(self.mesh, self.plan)
            if fresh is not None:
                self._fn = fresh
        from ..profiler import monitor
        monitor.counter("facade_train_step_rebuilds").add()
        return self

    @property
    def trace_count(self) -> int:
        """Compiled-executable count (0 before the first call) — the
        zero-recompiles-after-warmup observable."""
        if self._jit is None:
            return 0
        try:
            return self._jit._cache_size()
        except AttributeError:       # jax moved the private counter
            return -1


class _PipelineTrainStep(_ShardedTrainStep):
    """make_train_step's pp>1 flavor: the compiled fn is the full-manual
    pipelined step (parallel/pipeline_train.py) whose output carries a
    trailing schedule-measured bubble-fraction scalar. The wrapper
    strips it — callers see the facade triple — and publishes it as the
    `train.bubble_fraction` gauge at warmup (the 1F1B schedule is
    static per executable, so the warmup measurement IS the
    measurement; re-pulling it every step would add a host sync for a
    constant). A rebuild re-resolves the pipelined fn against the new
    mesh/plan through the base class's `_plan_rebuild` hook — ONE
    mechanism shared with the guard/instrumenter wrappers (the closure
    bakes the stage grid in, unlike the 3D step whose layouts live
    entirely in the pins); this subclass only resets the
    measurement."""

    def __init__(self, fn, mesh, plan, donate_argnums=(),
                 overlap=False):
        super().__init__(fn, mesh, plan, donate_argnums=donate_argnums,
                         overlap=overlap)
        self.bubble_fraction = None

    def __call__(self, params, opt_state, batch, *rest):
        out = super().__call__(params, opt_state, batch, *rest)
        if len(out) > 3 and self.bubble_fraction is None:
            import numpy as np
            from ..profiler import monitor
            self.bubble_fraction = float(np.asarray(out[3]))
            monitor.gauge("train.bubble_fraction").set(
                round(self.bubble_fraction, 6))
        return tuple(out[:3])

    def rebuild(self, mesh=None, plan=None):
        super().rebuild(mesh=mesh, plan=plan)
        self.bubble_fraction = None
        return self


class FacadeModel:
    _fwd_op_name = "model_forward"
    # decoder families name their serving family ("gpt"/"llama") so
    # generate() can build a continuous-batching engine over the same
    # params (inference/serving.py)
    _serving_family = None

    def __init__(self, cfg, init_fn, specs, seed=0):
        import jax
        from ..nn.parameter import Parameter
        self.cfg = cfg
        raw = init_fn(cfg, jax.random.PRNGKey(seed))
        self._param_names = tuple(raw.keys())
        self._params = {n: Parameter(v, name=f"{type(self).__name__}.{n}")
                        for n, v in raw.items()}
        for n, p in self._params.items():
            p.sharding_spec = specs[n]
        self.training = True

    def parameters(self):
        return list(self._params.values())

    def named_parameters(self, *a, **k):
        return list(self._params.items())

    def state_dict(self):
        return dict(self._params)

    def set_state_dict(self, sd):
        for k_, v in sd.items():
            if k_ in self._params:
                self._params[k_].set_value(
                    v.numpy() if hasattr(v, "numpy") else v)

    def train(self):
        self.training = True
        return self

    def eval(self):
        self.training = False
        return self

    def generate(self, prompts, max_new_tokens, num_slots=8,
                 max_len=None, temperature=0.0, top_k=0, eos_id=None,
                 max_top_k=0, seed=0, deadline_s=None,
                 deadline_ticks=None, max_ticks=None, spec_decode=None,
                 gamma=None, draft_layers=None, quant=None, mesh=None,
                 tp_axis="tp", **engine_kw):
        """Continuous-batching generation over this model's params
        (inference/serving.py): prompts is a list of 1-D int token-id
        sequences of MIXED lengths; returns one generated-id array per
        prompt, in order. The engine (slot pool + donated KV cache +
        compiled prefill/decode executables) is cached on the model and
        reused while the pool knobs AND the param values stay the same;
        set_value/load/train-step replace the underlying arrays, which
        the identity check below catches, rebuilding the engine so it
        never serves stale weights.

        SLO guardrails pass through: `deadline_s`/`deadline_ticks`
        bound each request, `max_ticks` bounds the drain (undelivered
        requests still resolve — never limbo), and `**engine_kw`
        reaches the ServingEngine (max_queue, queue_policy,
        queue_ttl_s, watchdog_timeout, guardrails, ... — part of the
        engine cache key, so switching knobs rebuilds).

        Speculative decoding passes through the same way:
        `spec_decode` ("auto"|"off"|"spec"), `gamma` (draft length)
        and `draft_layers` (self-draft depth) reach the ServingEngine
        (inference/spec_decode.py) and join the engine cache key —
        switching gamma or draft depth rebuilds the engine rather than
        serving a tick compiled for the old knobs.

        Quantized serving: `quant` ("auto"|"off"|"int8") selects the
        weight-only int8 path (inference/serving.py quant=) and joins
        the engine cache key — a quant engine compiled over the int8
        tree is never reused for fp serving or vice versa.

        Tensor-parallel serving: `mesh` (a jax Mesh with a `tp_axis`
        axis — parallel.mesh.build_mesh({'tp': N})) shards the engine's
        decode tick, KV pool and params over the mesh
        (inference/serving.py mesh=). The mesh TOPOLOGY (axis sizes,
        device order, tp_axis) joins the engine cache key: a resharded
        model silently reusing an engine compiled for another mesh (or
        for one device) would serve from the wrong layout."""
        for k, v in (("spec_decode", spec_decode), ("gamma", gamma),
                     ("draft_layers", draft_layers), ("quant", quant)):
            if v is not None:
                engine_kw[k] = v
        if self._serving_family is None:
            raise NotImplementedError(
                f"{type(self).__name__} is not a cached decoder family; "
                "generate() needs _serving_family")
        # mesh topology + tp degree, canonicalized (two meshes over the
        # same devices in the same order are the same engine; anything
        # else — axis sizes, device set/order, the tp axis name — is a
        # rebuild)
        mesh_key = None
        if mesh is not None:
            mesh_key = (str(tp_axis), tuple(mesh.shape.items()),
                        tuple(str(d) for d in mesh.devices.flat))
        from ..framework.dispatch import raw_value
        key = (num_slots, max_len, max_top_k, seed, mesh_key,
               tuple(sorted(engine_kw.items())),
               tuple(raw_value(self._params[n])
                     for n in self._param_names))
        eng = getattr(self, "_serving_engine", None)
        cached_key = getattr(self, "_serving_engine_key", None)
        if (eng is None or cached_key is None
                or len(cached_key) != 7
                or cached_key[:6] != key[:6]
                or any(a is not b
                       for a, b in zip(cached_key[6], key[6]))):
            from ..inference.serving import create_serving_engine
            eng = create_serving_engine(
                self, num_slots=num_slots, max_len=max_len,
                max_top_k=max_top_k, seed=seed, mesh=mesh,
                tp_axis=tp_axis, **engine_kw)
            self._serving_engine = eng
            self._serving_engine_key = key
        return eng.generate(prompts, max_new_tokens,
                            temperature=temperature, top_k=top_k,
                            eos_id=eos_id, deadline_s=deadline_s,
                            deadline_ticks=deadline_ticks,
                            max_ticks=max_ticks)

    def _dispatch(self, op_name, fn, *inputs):
        """fn(params_dict, *inputs) -> outputs; fn must not capture the
        model instance (close over the config value, not self)."""
        from ..framework.dispatch import apply
        names = self._param_names
        n_in = len(inputs)

        def _fwd(*vals, cfg_id=None, _fn=fn, _names=names, _n=n_in):
            return _fn(dict(zip(_names, vals[_n:])), *vals[:_n])
        _fwd.__qualname__ = f"{type(self).__name__}.{op_name}"
        return apply(op_name, _fwd, *inputs,
                     *[self._params[n] for n in names],
                     cfg_id=repr(self.cfg))
