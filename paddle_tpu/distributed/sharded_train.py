"""GSPMD-sharded training step.

This replaces the reference's entire distributed execution machinery for
collective mode — meta-optimizer program rewriting
(`sharding_optimizer.py:508`, `raw_program_optimizer.py:237`), the DDP
Reducer (`imperative/reducer.cc`), and comm-op insertion — with data
placement + one pjit:

- parameters are device_put with NamedShardings derived from `mesh_axes`
  tags (tensor/expert parallel) — GSPMD inserts TP collectives;
- batch inputs are sharded over (dp, sp) — data/sequence parallelism; the
  loss mean over a dp-sharded batch makes XLA emit the gradient allreduce
  (the Reducer's job) fused and overlapped by the latency-hiding scheduler;
- optimizer states are additionally sharded over dp (ZeRO-1/2 analog of
  `DygraphShardingOptimizer`): XLA all-gathers weights on use and
  reduce-scatters grads into the sharded update.
"""
import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

from ..core.tensor import Tensor
from ..core import autograd
from ..core.scope import scope
from ..core.random import rng_guard, default_generator
from ..jit import bind_tensors
from . import env


def shard_model(model, mesh=None, rules=None):
    """Place every parameter/buffer according to its mesh_axes tag
    (replicated if untagged). The analog of
    `fleet.distributed_model` (`fleet_base.py:881`). `rules` optionally
    tags untagged parameters first from a regex partition-rule list
    (`paddle_tpu.planner.rules` — planner output instead of
    hand-written per-layer tags)."""
    if rules is not None:
        from ..planner.rules import apply_partition_rules
        apply_partition_rules(model, rules)
    mesh = mesh or env.current_mesh()
    for n, p in model.named_parameters():
        if p is None:
            continue
        env.validate_param_axes(n, p)
        sh = env.param_sharding(p, mesh)
        p._value = jax.device_put(p._value, sh)
    for b in model.buffers():
        if b is not None:
            b._value = jax.device_put(b._value, env.replicated(mesh))
    return model


def shard_batch(batch, mesh=None, seq_axis=False):
    mesh = mesh or env.current_mesh()
    sh = env.batch_sharding(mesh, seq_axis)
    out = []
    for b in batch:
        v = b._value if isinstance(b, Tensor) else jnp.asarray(b)
        # env.trim_batch_sharding is SHARED with io.prefetch's device
        # stage: the no-redundant-h2d fast path below only fires when
        # both sides compute the identical target spec
        target = env.trim_batch_sharding(v, sh, mesh)
        # already-resident fast path: a batch the input pipeline placed
        # with the right sharding (io.prefetch_to_device with this mesh)
        # must NOT pay a second h2d/reshard hop on the step hot path
        cur = getattr(v, "sharding", None)
        if isinstance(v, jax.Array) and cur is not None:
            try:
                if cur.is_equivalent_to(target, v.ndim):
                    out.append(v)
                    continue
            except Exception:
                pass
        out.append(jax.device_put(v, target))
    return out


class ShardedTrainStep:
    """pjit'd fwd+bwd+update over the global mesh.

    zero_stage: 0 = replicated states (pure DP/TP); 1/2 = optimizer
    states sharded over dp (reference sharding stage1/2); 3 = PARAMETERS
    also sharded over dp — GSPMD then inserts the all-gather before each
    use and the reduce-scatter on the gradient, which IS ZeRO-3
    (reference `sharding_optimizer.py` stage 3 / `group_sharded`): no
    rank ever holds a full parameter copy between steps.

    offload: optimizer states live in HOST memory between steps
    (`pinned_host` memory kind, keeping their GSPMD spec — dp shards
    stay with their host) and visit HBM only around the update — the
    TPU-native form of the reference's optimizer-state CPU offload
    (`sharding/offload_helper.py`, `sharding_optimizer.py:464`
    _apply_optimize_offload_pass). The H2D/D2H hops are async
    device_puts bracketing the compiled step rather than in-graph
    placement annotations: the SPMD partitioner still rejects
    memory-kind round-trips inside a partitioned program on some
    backends, and the out-of-graph form is semantically identical.
    Composes with any zero_stage. Defaults come from the fleet
    DistributedStrategy when the optimizer is fleet-wrapped."""

    def __init__(self, model, loss_fn, optimizer, mesh=None, zero_stage=None,
                 seq_shard_batch=None, donate=True, offload=None,
                 lint=False, health=None, resilience=None, plan=None):
        # auto-sharding planner wiring: a paddle_tpu.planner.Plan (or
        # anything carrying .layout/.rules) configures zero_stage /
        # seq_shard_batch and re-tags untagged params from its verified
        # partition rules; explicit kwargs win over the plan's values
        self.plan = plan
        self.mesh = mesh or env.current_mesh()
        if plan is not None:
            # validate the mesh BEFORE touching the model: a rejected
            # plan must not leave its tags behind
            if self.mesh is not None:
                want = plan.layout.mesh_shape()
                have = {a: int(self.mesh.shape[a])
                        for a in self.mesh.axis_names}
                bad = {a: (s, have.get(a, 1)) for a, s in want.items()
                       if have.get(a, 1) != s}
                if bad:
                    raise ValueError(
                        f"mesh does not match the plan's layout "
                        f"{plan.layout.describe()}: axis sizes differ on "
                        f"{bad} — build the mesh with plan.build_mesh() "
                        "or pass the matching mesh")
            if zero_stage is None:
                zero_stage = int(plan.layout.zero_stage)
            if seq_shard_batch is None:
                seq_shard_batch = plan.layout.sp > 1
            from ..planner.rules import apply_partition_rules
            apply_partition_rules(model, plan.rules)
        if seq_shard_batch is None:
            seq_shard_batch = False
        self.model = model
        self.loss_fn = loss_fn
        self.optimizer = optimizer
        # fleet-wrapped optimizers carry the DistributedStrategy; its
        # sharding_configs are the reference's surface for stage/offload
        # (inert until strategy.sharding is on, reference semantics)
        strat = getattr(optimizer, "user_defined_strategy", None)
        scfg = (strat.sharding_configs
                if strat is not None and getattr(strat, "sharding", False)
                else {})
        if zero_stage is None:
            zero_stage = int(scfg.get("stage", 1))
        if offload is None:
            offload = bool(scfg.get("offload", False))
        self.zero_stage = zero_stage
        self.offload = offload
        self.seq_shard = seq_shard_batch
        named = [(n, p) for n, p in model.named_parameters()
                 if not p.stop_gradient]
        for n, p in named:
            # clear apply-time error (naming the parameter) instead of
            # an opaque trace-time shape failure from JAX
            env.validate_param_axes(n, p)
        self.param_names = [n for n, _ in named]
        self.params = [p for _, p in named]
        self.buffers = [b for _, b in model.named_buffers() if b is not None]
        for p in self.params:
            self.optimizer._get_state(p)
        if self.zero_stage >= 3:
            # stage 3: re-place the live parameters dp-sharded so the
            # persistent copies are 1/dp-sized from the start
            for p in self.params:
                p._value = jax.device_put(p._value, self._param_sharding(p))
        self._place_states()
        self._jitted = None
        self._donate = donate
        self._lint = lint
        self.lint_findings = None
        # health taps (see jit.TrainStep): the device-side stats reduce
        # over the SHARDED grads/params inside the pjit'd program — the
        # GSPMD partitioner inserts the cross-device reductions, so the
        # fetched scalars are already global
        from ..telemetry import health as _health
        self.health = _health.as_monitor(health)
        self._last_health = None
        # fault tolerance (see jit.TrainStep): step_boundary after every
        # completed step — periodic checkpoints + preemption exits.
        # restore() re-places arrays onto each live array's sharding, so
        # a ZeRO-3 resume comes back dp-sharded, not inflated
        from ..resilience.preempt import as_resilience
        self.resilience = as_resilience(resilience)
        if self.resilience is not None:
            self.resilience.attach(model, optimizer)
        if self.offload:
            # static per instance: precompute both memory-kind variants
            # so the per-step H2D/D2H hops don't rebuild NamedShardings
            # on the dispatch hot path
            self._host_state_sh = [self._state_sharding(p)
                                   for p in self.params]
            self._dev_state_sh = [self._state_sharding(p, device=True)
                                  for p in self.params]

    def _param_sharding(self, p):
        extra = "dp" if self.zero_stage >= 3 else None
        return env.param_sharding(p, self.mesh, extra_axis=extra)

    def _state_sharding(self, p, device=False):
        extra = "dp" if self.zero_stage >= 1 else None
        sh = env.param_sharding(p, self.mesh, extra_axis=extra)
        if self.offload and not device:
            sh = sh.with_memory_kind("pinned_host")
        return sh

    def _place_states(self):
        for p in self.params:
            st = self.optimizer._states[id(p)]
            sh = self._state_sharding(p)
            rep = env.replicated(self.mesh)
            for k, v in st.items():
                v = jnp.asarray(v)
                st[k] = jax.device_put(
                    v, sh if v.shape == tuple(p._value.shape) else rep)

    def _maybe_lint(self, batch):
        """Graph-doctor pre-flight: jaxpr lint of the traced step plus
        the sharding lint over the mesh + tags (one extra trace, no
        execution, no collective)."""
        if not self._lint or self.lint_findings is not None:
            return
        from ..analysis import emit
        from ..analysis.jaxpr_lint import lint_train_step
        from ..analysis.sharding_lint import lint_model_sharding
        findings = lint_train_step(self, *batch, mesh=self.mesh)
        findings += lint_model_sharding(
            zip(self.param_names, self.params), self.mesh,
            zero_stage=self.zero_stage)
        self.lint_findings = emit(findings, mode=self._lint,
                                  title="graph doctor [ShardedTrainStep]")

    def _build_step_fn(self, check_nan_inf=False, health_taps=False):
        params, buffers, opt = self.params, self.buffers, self.optimizer
        loss_fn = self.loss_fn
        model = self.model

        def step(param_vals, opt_states, buffer_vals, lr, rng, batch_vals):
            with autograd.fresh_tape(), \
                    bind_tensors(params, param_vals), \
                    bind_tensors(buffers, buffer_vals), rng_guard(rng):
                batch = [Tensor(v) for v in batch_vals]
                loss = loss_fn(*batch)
                # MoE routing-health taps: the forward above left the
                # per-layer stats on the MoE layers; collect them as a
                # device-side aux output (same pattern as health taps)
                collect = getattr(model, "collect_moe_stats", None)
                mstats = collect() if collect is not None else None
                autograd.backward(loss)
                grads = [p.grad._value if p.grad is not None
                         else jnp.zeros_like(p._value) for p in params]
                # compiled FLAGS_check_nan_inf (the eager per-op scan can't
                # see inside the pjit'd step); a poisoned step keeps old
                # params/opt-state (the inputs are donated)
                checks = None
                if check_nan_inf:
                    checks = (jnp.isfinite(loss._value).all(),
                              jnp.stack([jnp.all(jnp.isfinite(g))
                                         for g in grads])
                              if grads else jnp.ones((0,), jnp.bool_))
                # health taps see the raw (pre-clip) grads
                raw_grads = grads if health_taps else None
                with autograd.no_grad(), scope("optimizer"):
                    if opt._grad_clip is not None:
                        pg = opt._grad_clip(
                            [(p, Tensor(g)) for p, g in zip(params, grads)])
                        grads = [g._value for _, g in pg]
                    new_vals, new_states = opt._functional_apply(
                        params, param_vals, grads, opt_states, lr)
                if check_nan_inf:
                    ok = jnp.logical_and(checks[0], jnp.all(checks[1]))
                    new_vals = [jnp.where(ok, n, o)
                                for n, o in zip(new_vals, param_vals)]
                    new_states = jax.tree_util.tree_map(
                        lambda n, o: jnp.where(ok, n, o),
                        new_states, opt_states)
                hstats = None
                if health_taps:
                    from ..telemetry.health import device_health_stats
                    hstats = device_health_stats(
                        loss._value, raw_grads, new_vals, param_vals)
                new_buf = [b._value for b in buffers]
                return (loss._value, new_vals, new_states, new_buf,
                        checks, hstats, mstats)

        return step

    def _make_step(self, check_nan_inf=False, health_taps=False):
        params, buffers, opt = self.params, self.buffers, self.optimizer
        mesh = self.mesh
        param_sh = [self._param_sharding(p) for p in params]
        state_sh = []
        for p in params:
            # the compiled step always sees device-memory states; with
            # offload the host<->device hops happen in __call__
            psh = self._state_sharding(p, device=True)
            rep = env.replicated(mesh)
            st = opt._states[id(p)]
            state_sh.append({k: (psh if np.shape(v) == tuple(p._value.shape)
                                 else rep) for k, v in st.items()})
        buf_sh = [env.replicated(mesh)] * len(buffers)
        rep = env.replicated(mesh)
        in_sh = (param_sh, state_sh, buf_sh, rep, rep, None)
        out_sh = (rep, param_sh, state_sh, buf_sh, None, None, None)
        donate = (0, 1, 2) if self._donate else ()
        return jax.jit(self._build_step_fn(check_nan_inf=check_nan_inf,
                                           health_taps=health_taps),
                       in_shardings=in_sh, out_shardings=out_sh,
                       donate_argnums=donate)

    def __call__(self, *batch):
        # flight-recorder integration (see jit.TrainStep.__call__): a
        # context-active TelemetryRecorder records this step too
        from .. import telemetry
        with telemetry.auto_step() as _tw:
            if self.health is not None:
                with self.health.guard(_tw) as g:
                    out = self._run_step(*batch)
                    g.stage(self._last_health)
            else:
                out = self._run_step(*batch)
            if getattr(self, "_last_moe", None) is not None:
                from ..moe.stats import note_step_stats
                note_step_stats(_tw, self._last_moe,
                                getattr(self.model, "moe_num_experts",
                                        None))
            _tw.note(loss=out)
        if self.resilience is not None:
            self.resilience.step_boundary(loss=out)
        return out

    def _run_step(self, *batch):
        from .. import telemetry
        from ..flags import get_flag
        check = get_flag("check_nan_inf")
        taps = self.health is not None
        key = (check, taps)
        if self._jitted is None or getattr(self, "_check_key", None) != key:
            self._maybe_lint(batch)
            self._jitted = self._make_step(check_nan_inf=check,
                                           health_taps=taps)
            self._check_key = key
        with telemetry.span("sharded.shard_batch", cat="h2d"):
            batch_vals = shard_batch(batch, self.mesh, self.seq_shard)
        param_vals = [p._value for p in self.params]
        opt_states = [self.optimizer._states[id(p)] for p in self.params]
        buffer_vals = [b._value for b in self.buffers]
        if self.offload:
            # async H2D: bring host-resident states onto the chip for the
            # update (device_put returns immediately; the transfer
            # overlaps the batch sharding / dispatch work above)
            with telemetry.span("sharded.offload_h2d", cat="h2d"):
                opt_states = [
                    {k: jax.device_put(v, dsh)
                     if getattr(getattr(v, "sharding", None), "memory_kind",
                                None) == "pinned_host" else v
                     for k, v in st.items()}
                    for dsh, st in zip(self._dev_state_sh, opt_states)]
        lr = jnp.asarray(self.optimizer.get_lr(), jnp.float32)
        rng = default_generator().split()
        # compile observatory (see jit.TrainStep._run_step): records
        # every pjit (re)compile with cause diff + memory/cost analysis
        from ..telemetry import compile_obs
        with telemetry.span("sharded.step_dispatch", cat="dispatch"):
            (loss, new_vals, new_states, new_buf, checks,
             hstats, mstats) = compile_obs.dispatch(
                f"{type(self).__name__}[{type(self.model).__name__}]",
                self._jitted,
                (param_vals, opt_states, buffer_vals, lr, rng, batch_vals),
                arg_names=("params", "opt_states", "buffers", "lr",
                           "rng", "batch"),
                static={"check_nan_inf": check, "health_taps": taps,
                        "zero_stage": self.zero_stage,
                        "offload": self.offload},
                donate=(0, 1, 2) if self._donate else ())
        self._last_health = hstats
        self._last_moe = mstats
        if self.offload:
            # async D2H: evict the updated states back to pinned_host so
            # HBM is free of them between steps
            with telemetry.span("sharded.offload_d2h", cat="d2h"):
                new_states = [
                    {k: jax.device_put(v, hsh)
                     if np.shape(v) == tuple(nv.shape) else v
                     for k, v in st.items()}
                    for hsh, nv, st in zip(self._host_state_sh, new_vals,
                                           new_states)]
        for p, v in zip(self.params, new_vals):
            p._value = v
            p.grad = None
        for p, s in zip(self.params, new_states):
            self.optimizer._states[id(p)] = s
        for b, v in zip(self.buffers, new_buf):
            b._value = v
        if checks is not None:
            from ..jit import TrainStep
            TrainStep._report_non_finite(self, checks)
        return Tensor(loss)
