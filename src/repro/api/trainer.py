"""LocalTrainer: owns the optimizer and the compiled local-step functions.

This replaces the old module-level ``train_steps`` helper, which received
its optimizer through a mutable function attribute (``train_steps.opt``) —
non-reentrant state that made the drivers unshardable and impossible to
interleave. The trainer is a plain object; two trainers never share
mutable state, and compiled steps are reused through a process-wide cache
keyed by (loss_fn, FedConfig, optimizer spec, pool backend), so repeated
runs over the same model recompile nothing.
"""
from __future__ import annotations

import functools
from collections import OrderedDict
from typing import Any, Callable, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.api.pools import PoolBackend, backend_for
from repro.api.results import ModelRecord
from repro.configs.base import FedConfig
from repro.core import distances as D
from repro.kernels.local_step import fused_loss_for
from repro.data.plan import (DataPlan, stack_plan_arrays,
                             stack_plan_indices)
from repro.optim import make_optimizer
from repro.optim.optimizers import Optimizer
from repro.sharding.specs import can_shard_flat, shard_map_flat

PyTree = Any


def hp_regularized_loss(loss_fn: Callable, fed: FedConfig,
                        backend: PoolBackend) -> Callable:
    """Eq. 9 with (α, β) as *traced arguments* instead of baked constants:
    ``full_loss(params, batch, pool, alpha, beta)``. The batched engine
    threads per-run (α, β) vectors through one compiled program (the Fig. 10
    grid); the sequential path closes over ``fed.alpha``/``fed.beta`` —
    multiplying by a traced scalar and by the equal Python constant produce
    the same bits, so both paths share this core."""

    def full_loss(params, batch, pool, alpha, beta):
        task = loss_fn(params, batch)
        total = task
        with jax.named_scope(obs.REG):
            if fed.use_d1:
                d1 = backend.d1(params, pool, fed.distance_measure)
                if fed.log_scale_distances:
                    d1 = D.log_scale(d1, task)
                total = total - alpha * d1
            if fed.use_d2:
                d2 = D.d2_anchor_distance(params, pool.first(),
                                          fed.distance_measure)
                if fed.log_scale_distances:
                    d2 = D.log_scale(d2, task)
                total = total + beta * d2
        return total, task

    return full_loss


def regularized_loss(loss_fn: Callable, fed: FedConfig,
                     backend: PoolBackend) -> Callable:
    """Eq. 9: L(m) = ℓ(m; D_i) − α·d1 + β·d2, with the appendix's
    log-calibration. d1 comes from the pool backend, so any registered
    representation plugs in without touching this function."""
    hp_loss = hp_regularized_loss(loss_fn, fed, backend)

    def full_loss(params, batch, pool):
        return hp_loss(params, batch, pool, fed.alpha, fed.beta)

    return full_loss


def make_plain_step(loss_fn: Callable, opt: Optimizer):
    """Jitted (params, opt_state, batch, step) → (params, opt_state, task).
    Donates params/opt_state; callers must pass fresh buffers."""

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def step_fn(params, opt_state, batch, step):
        task, grads = jax.value_and_grad(loss_fn)(params, batch)
        params, opt_state = opt.update(params, grads, opt_state, step)
        return params, opt_state, task

    return step_fn


def make_pool_step(loss_fn: Callable, fed: FedConfig, opt: Optimizer,
                   backend: PoolBackend):
    """Jitted regularized step; the pool rides along as a pytree argument
    so one compilation serves every client/model."""
    full_loss = regularized_loss(loss_fn, fed, backend)

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def step_fn(params, opt_state, batch, pool, step):
        (_, task), grads = jax.value_and_grad(
            lambda p: full_loss(p, batch, pool), has_aux=True)(params)
        params, opt_state = opt.update(params, grads, opt_state, step)
        return params, opt_state, task

    return step_fn


def vmap_step(one_step: Callable, n_stacked_extras: int = 0):
    """Lift a per-run step ``(params, opt_state, batch, *extras, step)``
    into the batched-step contract: jitted vmap over a leading run axis on
    params/opt_state/batch (and on ``n_stacked_extras`` trailing pytree
    args — e.g. MetaFed's per-run anchor), with the step counter held
    scalar. Donates params/opt_state like every compiled step. The plan
    interpreter's custom ``batched_step_factory`` hooks build on this so a
    strategy's batched variant is *exactly* its sequential graph under
    ``vmap`` — the bit-identity contract `run_batch` tests rely on."""
    axes = (0, 0, 0) + (0,) * n_stacked_extras + (None,)
    return jax.jit(jax.vmap(one_step, in_axes=axes), donate_argnums=(0, 1))


def _vmapped_plain_step(loss_fn: Callable, opt: Optimizer):
    """Unjitted vmapped plain step — every argument except the step counter
    carries a leading run axis. The building block `make_batched_plain_step`
    jits and the shard-mapped fleet path wraps per device slice."""

    def one_step(params, opt_state, batch, step):
        task, grads = jax.value_and_grad(loss_fn)(params, batch)
        params, opt_state = opt.update(params, grads, opt_state, step)
        return params, opt_state, task

    return jax.vmap(one_step, in_axes=(0, 0, 0, None))


def make_batched_plain_step(loss_fn: Callable, opt: Optimizer):
    """Vmapped variant of ``make_plain_step``: every argument except the
    step counter carries a leading run axis, so B independent runs advance
    in one dispatch. Per-slice math is the unbatched step's graph under
    ``vmap`` — the bit-identity contract `run_batch` tests rely on."""
    return jax.jit(_vmapped_plain_step(loss_fn, opt), donate_argnums=(0, 1))


def _vmapped_pool_step(loss_fn: Callable, fed: FedConfig, opt: Optimizer,
                       backend: PoolBackend):
    """Unjitted vmapped regularized step (see `_vmapped_plain_step`)."""
    full_loss = hp_regularized_loss(loss_fn, fed, backend)

    def one_step(params, opt_state, batch, pool, alpha, beta, step):
        (_, task), grads = jax.value_and_grad(
            lambda p: full_loss(p, batch, pool, alpha, beta),
            has_aux=True)(params)
        params, opt_state = opt.update(params, grads, opt_state, step)
        return params, opt_state, task

    return jax.vmap(one_step, in_axes=(0, 0, 0, 0, 0, 0, None))


def make_batched_pool_step(loss_fn: Callable, fed: FedConfig, opt: Optimizer,
                           backend: PoolBackend):
    """Vmapped regularized step: stacked params/opt-state/batches/pools plus
    per-run (α, β) vectors — a whole seed sweep or (α, β) grid is one jitted
    program instead of |sweep| sequential dispatches."""
    return jax.jit(_vmapped_pool_step(loss_fn, fed, opt, backend),
                   donate_argnums=(0, 1))


# ---------------------------------------------------------------------------
# Scan-compiled variants: the whole local phase as ONE program. Batches are
# jit-internal gathers from a DataPlan's device-resident arrays (indexed by
# its precomputed shuffle schedule), so the dispatch-per-step and the
# host→device upload per batch both disappear. The step bodies are the same
# graphs the per-step functions trace, rolled into `lax.scan` — bit-identity
# with the iterator path is the acceptance contract (tests/test_dataplan.py).
# ---------------------------------------------------------------------------

def _gather(arrays: PyTree, row: jax.Array) -> PyTree:
    return jax.tree.map(lambda a: a[row], arrays)


@jax.custom_batching.custom_vmap
def _runtime_barrier(xs):
    """`lax.optimization_barrier` with a vmap rule (this jax version has
    none): barrier the batched arrays directly — identity either way."""
    return jax.lax.optimization_barrier(xs)


@_runtime_barrier.def_vmap
def _runtime_barrier_vmap(axis_size, in_batched, xs):
    return jax.lax.optimization_barrier(xs), in_batched[0]


def _scan1(body: Callable, carry, xs):
    """`lax.scan`, except a single-row xs applies the body directly. XLA
    deletes trip-count-1 while loops and then fuses across the former loop
    boundary differently from the dispatched per-step program (observed on
    the conv model: the backward and the Adam update contract FMAs across
    the unrolled boundary, a 1-ULP divergence) — which would break the
    scanned-vs-per-step bit-identity contract exactly in the one-step-phase
    corner (e.g. `e_warmup=1` visits, pool_size=1 runs). Applying the body
    once traces the same graph the per-step path compiles — behind an
    optimization barrier, so trace-time constants in xs (the step counter
    from `jnp.arange`) stay runtime values exactly like scan loop
    variables, instead of constant-folding through the Adam bias
    correction with different rounding."""
    n = jax.tree.leaves(xs)[0].shape[0]
    if n == 1:
        x0 = _runtime_barrier(jax.tree.map(lambda a: a[0], xs))
        carry, y = body(carry, x0)
        return carry, jax.tree.map(lambda a: a[None], y)
    return jax.lax.scan(body, carry, xs)


def _scan_steps(task_and_grads: Callable, opt: Optimizer, params: PyTree,
                arrays: PyTree, idx: jax.Array):
    """Shared scan over (n_steps, batch) index rows from a fresh optimizer
    state — the one step body every scanned core runs: gather the batch,
    take (task, grads), apply the optimizer. Returns (params, (n,) tasks)."""
    def body(carry, si):
        p, o = carry
        s, row = si
        with jax.named_scope(obs.TASK):
            batch = _gather(arrays, row)
        task, grads = task_and_grads(p, batch)
        p, o = opt.update(p, grads, o, s)
        return (p, o), task

    (params, _), tasks = _scan1(
        body, (params, opt.init(params)), (jnp.arange(idx.shape[0]), idx))
    return params, tasks


def _scanned_train_core(loss_fn: Callable, opt: Optimizer) -> Callable:
    """(params, arrays, idx) → (params, last task): `make_plain_step`'s body
    scanned over the (n_steps, batch) index rows. `loss_fn` arrives already
    resolved through the capability probe (`_compiled_steps`), so for conv
    models this body contains only pad/slice/GEMM — no `lax.conv`, no
    conv-in-scan cliff (kernels/local_step.py, DESIGN.md §9)."""

    def core(params, arrays, idx):
        params, tasks = _scan_steps(jax.value_and_grad(loss_fn), opt,
                                    params, arrays, idx)
        return params, tasks[-1]

    return core


def _scanned_local_core(loss_fn: Callable, fed: FedConfig, opt: Optimizer,
                        backend: PoolBackend) -> Callable:
    """(m_in, arrays, idx, α, β) → (pool average, pool, (S,) tasks): the
    paper's entire local procedure (Alg. 1 lines 3–17) as a scan over pool
    slots nested around a scan over steps. The pool pytree is the outer
    carry (fixed-capacity NamedTuple — structure is static; this holds for
    the factor-form `LowRankDeltaPool` too: its U/V/dense dicts are keyed
    by static leaf index and the truncated-rank append is QR on fixed
    shapes, so the same nested scan carries factor pools unchanged), so
    S × e_local dispatches collapse into one compiled program. α/β ride traced, like
    the batched steps — same bits as the baked constants. Like
    `_scanned_train_core`, `loss_fn` is the probe-resolved step loss —
    conv models scan their fused GEMM twin here."""
    full_loss = hp_regularized_loss(loss_fn, fed, backend)

    def core(m_in, arrays, idx, alpha, beta):
        # idx: (S, e_local, batch)
        def slot(pool, idx_j):
            def task_and_grads(p, batch):
                (_, task), grads = jax.value_and_grad(
                    lambda p_: full_loss(p_, batch, pool, alpha, beta),
                    has_aux=True)(p)
                return task, grads

            m, tasks = _scan_steps(task_and_grads, opt,
                                   pool.average(),     # Eq. 6 init
                                   arrays, idx_j)
            return pool.append(m), tasks[-1]

        pool, tasks = _scan1(slot, backend.create(m_in, fed), idx)
        return pool.average(), pool, tasks

    return core


class _CompiledSteps(NamedTuple):
    opt: Optimizer
    pool_step: Callable
    plain_step: Callable
    batched_pool_step: Callable
    batched_plain_step: Callable
    scanned_plain: Callable
    scanned_local: Callable
    batched_scanned_plain: Callable
    batched_scanned_local: Callable
    # unjitted vmapped cores — what `sharded_program` puts under shard_map
    # when a mesh is passed to the batched entry points. Stored here (not
    # rebuilt per call) so the sharded-program cache keys stay stable and
    # each (core, mesh) pair compiles exactly once per process.
    vm_plain_step: Callable
    vm_pool_step: Callable
    vm_scanned_plain: Callable
    vm_scanned_local: Callable


class StepKey(NamedTuple):
    """Typed step-cache key. A NamedTuple (not an ad-hoc tuple) so the
    optimizer-override fields have *named positions* — an override passed in
    a different order can never alias another config's entry — and so the
    batched variants live inside the same ``_CompiledSteps`` value instead
    of doubling the cache footprint with a second key shape."""
    loss_fn: Callable
    fed: FedConfig
    opt_name: str
    lr: float
    wd: float
    backend_name: str


# StepKey → _CompiledSteps, bounded LRU. The jitted steps close over
# loss_fn, so a weak-keyed cache could never evict (the value keeps its own
# key alive); a size cap bounds the retained compiled executables instead.
# ``jax.jit`` wrappers are lazy: the batched variants cost nothing until a
# ``run_batch`` call actually traces them.
_STEP_CACHE: "OrderedDict[StepKey, _CompiledSteps]" = OrderedDict()
_STEP_CACHE_MAX = 8


def _compiled_steps(loss_fn: Callable, fed: FedConfig, opt_name: str,
                    lr: float, wd: float,
                    backend: PoolBackend) -> _CompiledSteps:
    def build():
        opt = make_optimizer(opt_name, lr, wd)
        # per-model capability probe: conv models registered a scan-safe
        # GEMM-formulated loss twin (kernels/local_step.py) and route every
        # step through it; matmul models resolve to themselves and keep
        # their current step bodies. EVERY variant — per-step, scanned,
        # batched, shard-mapped — is built over the SAME resolved loss, so
        # the cross-path bit-identity contracts hold by construction.
        # Its ops, and their backward, are named step.task (repro.obs).
        step_loss = jax.named_scope(obs.TASK)(fused_loss_for(loss_fn))
        plain_core = _scanned_train_core(step_loss, opt)
        local_core = _scanned_local_core(step_loss, fed, opt, backend)
        vm_plain = _vmapped_plain_step(step_loss, opt)
        vm_pool = _vmapped_pool_step(step_loss, fed, opt, backend)
        return _CompiledSteps(
            opt=opt,
            pool_step=make_pool_step(step_loss, fed, opt, backend),
            plain_step=make_plain_step(step_loss, opt),
            batched_pool_step=jax.jit(vm_pool, donate_argnums=(0, 1)),
            batched_plain_step=jax.jit(vm_plain, donate_argnums=(0, 1)),
            scanned_plain=jax.jit(plain_core),
            scanned_local=jax.jit(local_core),
            batched_scanned_plain=jax.jit(
                jax.vmap(plain_core, in_axes=(0, 0, 0))),
            batched_scanned_local=jax.jit(
                jax.vmap(local_core, in_axes=(0, 0, 0, 0, 0))),
            vm_plain_step=vm_plain,
            vm_pool_step=vm_pool,
            vm_scanned_plain=jax.vmap(plain_core, in_axes=(0, 0, 0)),
            vm_scanned_local=jax.vmap(local_core, in_axes=(0, 0, 0, 0, 0)))

    key = StepKey(loss_fn, fed, opt_name, lr, wd, backend.name)
    try:
        cached = _STEP_CACHE.get(key)
    except TypeError:            # loss_fn not hashable: skip the cache
        return build()
    if cached is None:
        cached = build()
        _STEP_CACHE[key] = cached
        while len(_STEP_CACHE) > _STEP_CACHE_MAX:
            _STEP_CACHE.popitem(last=False)
    else:
        _STEP_CACHE.move_to_end(key)
    return cached


# (vm_fn, mesh, leading, donate) → jit(shard_map(vm_fn)), bounded LRU.
# Sharded programs are built on demand the first time a batched entry point
# sees a given (core, mesh) pair — a fleet sweep reuses one compiled
# program across every cohort/round instead of re-wrapping per call.
_SHARDED_CACHE: "OrderedDict[tuple, Callable]" = OrderedDict()
_SHARDED_CACHE_MAX = 16


def sharded_program(vm_fn: Callable, mesh, leading: Tuple[bool, ...],
                    donate: Tuple[int, ...] = ()) -> Callable:
    """`jax.jit(shard_map_flat(vm_fn, mesh, leading))`, cached process-wide.
    `vm_fn` must be a *stable* callable (one of the `_CompiledSteps.vm_*`
    cores, or a per-call custom step) whose flagged arguments carry the
    flattened run×client leading axis. Each device runs the vmapped core on
    its slice — per-run math never crosses the axis, so results are
    bit-identical to the single-program vmap path."""
    key = (vm_fn, mesh, tuple(leading), tuple(donate))
    fn = _SHARDED_CACHE.get(key)
    if fn is None:
        fn = jax.jit(shard_map_flat(vm_fn, mesh, leading),
                     donate_argnums=tuple(donate))
        _SHARDED_CACHE[key] = fn
        while len(_SHARDED_CACHE) > _SHARDED_CACHE_MAX:
            _SHARDED_CACHE.popitem(last=False)
    else:
        _SHARDED_CACHE.move_to_end(key)
    return fn


# Jitted batched pool operations, shared process-wide: an *eager* vmap here
# would re-trace per call and dispatch unfused per-leaf ops — measured ~100×
# the jitted cost on an MLP-sized model, enough to erase the whole batching
# win. jax.jit caches per pool treedef/shape, so every backend gets its own
# compiled version on first use.
_batched_pool_average = jax.jit(jax.vmap(lambda pool: pool.average()))
_batched_pool_append = jax.jit(jax.vmap(lambda pool, m: pool.append(m)))


class LocalTrainer:
    """Per-run training engine: optimizer + compiled steps + pool procedure.

    `optimizer` / `learning_rate` / `weight_decay` override the FedConfig
    values (baselines like DFedAvgM train with their own local optimizer
    while sharing the rest of the config).
    """

    def __init__(self, loss_fn: Callable, fed: FedConfig, *,
                 optimizer: Optional[str] = None,
                 learning_rate: Optional[float] = None,
                 weight_decay: Optional[float] = None):
        self.loss_fn = loss_fn
        self.fed = fed
        self.backend = backend_for(fed)
        compiled = _compiled_steps(
            loss_fn, fed,
            optimizer if optimizer is not None else fed.optimizer,
            learning_rate if learning_rate is not None else fed.learning_rate,
            weight_decay if weight_decay is not None else fed.weight_decay,
            self.backend)
        self.opt = compiled.opt
        self.pool_step = compiled.pool_step
        self.plain_step = compiled.plain_step
        self.batched_pool_step = compiled.batched_pool_step
        self.batched_plain_step = compiled.batched_plain_step
        self.scanned_plain = compiled.scanned_plain
        self.scanned_local = compiled.scanned_local
        self.batched_scanned_plain = compiled.batched_scanned_plain
        self.batched_scanned_local = compiled.batched_scanned_local
        self.vm_plain_step = compiled.vm_plain_step
        self.vm_pool_step = compiled.vm_pool_step
        self.vm_scanned_plain = compiled.vm_scanned_plain
        self.vm_scanned_local = compiled.vm_scanned_local
        self._batched_opt_init = jax.jit(jax.vmap(self.opt.init))
        self._batched_pool_create = jax.jit(
            jax.vmap(lambda m: self.backend.create(m, self.fed)))

    # -- step loop ----------------------------------------------------------

    def train(self, params: PyTree, data_iter, n_steps: int, *,
              pool: Any = None,
              step_fn: Optional[Callable] = None
              ) -> Tuple[PyTree, jax.Array]:
        """Run n_steps of SGD from a fresh optimizer state. With `pool`,
        uses the regularized step; `step_fn` overrides the step entirely
        (signature (params, opt_state, batch, step), e.g. a SAM step).
        The returned task loss is a jax scalar — converting it blocks on
        the device, so callers defer `float()` to record-construction
        time (a per-call sync here serializes every dispatch)."""
        params = jax.tree.map(jnp.copy, params)   # steps donate buffers
        opt_state = self.opt.init(params)
        task = jnp.zeros(())
        for s in range(n_steps):
            batch = next(data_iter)
            if step_fn is not None:
                params, opt_state, task = step_fn(params, opt_state, batch,
                                                  jnp.int32(s))
            elif pool is None:
                params, opt_state, task = self.plain_step(
                    params, opt_state, batch, jnp.int32(s))
            else:
                params, opt_state, task = self.pool_step(
                    params, opt_state, batch, pool, jnp.int32(s))
        return params, task

    def train_scanned(self, params: PyTree, plan: DataPlan,
                      n_steps: int) -> Tuple[PyTree, jax.Array]:
        """Plain `train` as ONE compiled program: the plan's next n_steps
        index rows drive a `lax.scan` whose body gathers each batch from
        the device-resident arrays — no per-step dispatch or host
        round-trip. Bit-identical to `train` over the equivalent iterator.
        (Pool-regularized training has no single-model scanned form; the
        whole pool procedure is `local_client_train_scanned`.)"""
        with obs.span(obs.TAKE):
            idx = plan.take(n_steps)
        with obs.span(obs.DISPATCH):
            return self.scanned_plain(params, plan.arrays, idx)

    # -- paper Alg. 1 lines 3–17 -------------------------------------------

    def local_client_train(self, m_in: PyTree, data_iter, *,
                           on_model_end: Optional[Callable] = None,
                           ) -> Tuple[PyTree, Any, List[ModelRecord]]:
        """One client's full local procedure: seed the pool with the
        incoming model, train S diversity-regularized models, return
        (pool average, pool, per-model records). With use_pool=False
        (ablation row "no pool" == FedSeq) trains one plain model.
        `on_model_end(record, params)` fires after each pool model; it
        may fill `record.val_metric` with a per-model validation score."""
        fed = self.fed
        if not fed.use_pool:
            params, _ = self.train(m_in, data_iter, fed.e_local)
            return params, None, []

        pool = self.backend.create(m_in, fed)
        tasks: List[jax.Array] = []
        records: List[ModelRecord] = []
        for j in range(fed.pool_size):          # train S models
            m_j = pool.average()                # Eq. 6 init
            m_j, task = self.train(m_j, data_iter, fed.e_local, pool=pool)
            pool = pool.append(m_j)
            if on_model_end is not None:
                # the callback observes a complete record — this is the
                # one path that still syncs per model, by contract
                rec = ModelRecord(index=j, task_loss=float(task))
                records.append(rec)
                on_model_end(rec, m_j)
            else:
                tasks.append(task)
        if on_model_end is None:
            # single deferred sync: every model's dispatches are already
            # queued before the first float() blocks
            with obs.span(obs.SYNC):
                records = [ModelRecord(index=j, task_loss=float(t))
                           for j, t in enumerate(tasks)]
        return pool.average(), pool, records

    def local_client_train_scanned(self, m_in: PyTree, plan: DataPlan,
                                   ) -> Tuple[PyTree, Any,
                                              List[ModelRecord]]:
        """`local_client_train` as ONE compiled program: S pool models ×
        e_local steps — pool average init, regularized step, pool append —
        scanned with the pool pytree as carry. Bit-identical to the
        iterator path on the equivalent stream (the acceptance contract);
        callers needing per-model callbacks use `local_client_train`."""
        fed = self.fed
        if not fed.use_pool:
            params, _ = self.train_scanned(m_in, plan, fed.e_local)
            return params, None, []
        with obs.span(obs.TAKE):
            idx = plan.take(fed.pool_size * fed.e_local).reshape(
                fed.pool_size, fed.e_local, plan.batch_size)
        with obs.span(obs.DISPATCH):
            avg, pool, tasks = self.scanned_local(
                m_in, plan.arrays, idx, jnp.float32(fed.alpha),
                jnp.float32(fed.beta))
        with obs.span(obs.SYNC):
            records = [ModelRecord(index=j, task_loss=float(t))
                       for j, t in enumerate(np.asarray(tasks))]
        return avg, pool, records

    # -- batched variants (B independent runs, leading run axis) ------------

    def train_batched(self, params: PyTree, data_iters: List[Any],
                      n_steps: int, *, pools: Any = None,
                      alphas: Optional[jax.Array] = None,
                      betas: Optional[jax.Array] = None,
                      step_fn: Optional[Callable] = None,
                      mesh: Any = None,
                      ) -> Tuple[PyTree, jax.Array]:
        """`train` over a stacked (B, …) params pytree and B data iterators:
        each step stacks one batch per run and advances all runs in a single
        vmapped dispatch. With `mesh` (and B divisible by its data-axis
        device count) the dispatch goes under `shard_map` — each device
        advances its slice of the batch, bit-identically to the single-device
        path. Returns (stacked params, (B,) last task losses)."""
        shard = can_shard_flat(mesh, len(data_iters))
        if step_fn is not None:
            step = (sharded_program(step_fn, mesh,
                                    (True, True, True, False), (0, 1))
                    if shard else step_fn)
        elif pools is None:
            step = (sharded_program(self.vm_plain_step, mesh,
                                    (True, True, True, False), (0, 1))
                    if shard else self.batched_plain_step)
        else:
            step = (sharded_program(self.vm_pool_step, mesh,
                                    (True,) * 6 + (False,), (0, 1))
                    if shard else self.batched_pool_step)
        params = jax.tree.map(jnp.copy, params)   # steps donate buffers
        opt_state = self._batched_opt_init(params)
        task = jnp.zeros((len(data_iters),))
        for s in range(n_steps):
            batch = stack_trees([next(it) for it in data_iters])
            if step_fn is not None or pools is None:
                params, opt_state, task = step(
                    params, opt_state, batch, jnp.int32(s))
            else:
                params, opt_state, task = step(
                    params, opt_state, batch, pools, alphas, betas,
                    jnp.int32(s))
        return params, task

    def local_client_train_batched(self, m_in: PyTree, data_iters: List[Any],
                                   alphas: jax.Array, betas: jax.Array, *,
                                   mesh: Any = None,
                                   ) -> Tuple[PyTree, Any,
                                              List[List[ModelRecord]]]:
        """`local_client_train` over B runs at once: B pools seeded from the
        stacked incoming models, S diversity-regularized models trained per
        run in lockstep (the loop structure is static across the batch —
        enforced by `run_batch`'s grouping). Returns (stacked pool averages,
        stacked pools, per-run ModelRecord lists)."""
        fed = self.fed
        b = len(data_iters)
        if not fed.use_pool:
            params, task = self.train_batched(m_in, data_iters, fed.e_local,
                                              mesh=mesh)
            return params, None, [[] for _ in range(b)]

        pools = self._batched_pool_create(m_in)
        tasks: List[jax.Array] = []
        for j in range(fed.pool_size):          # train S models per run
            m_j = _batched_pool_average(pools)
            m_j, task = self.train_batched(m_j, data_iters, fed.e_local,
                                           pools=pools, alphas=alphas,
                                           betas=betas, mesh=mesh)
            pools = _batched_pool_append(pools, m_j)
            tasks.append(task)
        # one deferred sync for the whole (S, B) loss grid — per-element
        # float(task[i]) inside the loop forced S·B blocking transfers
        records = _model_records(jnp.stack(tasks), b)
        return _batched_pool_average(pools), pools, records

    # -- scanned batched variants (DataPlans, stacked run axis) --------------

    def train_scanned_batched(self, params: PyTree, plans: List[DataPlan],
                              n_steps: int, *, arrays: Any = None,
                              mesh: Any = None,
                              ) -> Tuple[PyTree, jax.Array]:
        """`train_scanned` over B runs: stacked index tensors drive one
        vmapped scan — the whole group's phase is a single dispatch, with
        no per-step host `stack_trees` re-upload. `arrays` lets the
        caller reuse a stacked-arrays pytree across visits. With `mesh`,
        the scan goes under `shard_map` (each device scans its slice)."""
        if arrays is None:
            arrays = stack_plan_arrays(plans)
        idx = stack_plan_indices(plans, n_steps)
        fn = (sharded_program(self.vm_scanned_plain, mesh, (True,) * 3)
              if can_shard_flat(mesh, len(plans))
              else self.batched_scanned_plain)
        return fn(params, arrays, idx)

    def local_client_train_scanned_batched(self, m_in: PyTree,
                                           plans: List[DataPlan],
                                           alphas: jax.Array,
                                           betas: jax.Array, *,
                                           arrays: Any = None,
                                           mesh: Any = None,
                                           ) -> Tuple[PyTree, Any,
                                                      List[List[ModelRecord]]]:
        """`local_client_train_scanned` over B runs in one vmapped scan
        program (B × S × e_local steps, one dispatch). With `mesh`, the
        program goes under `shard_map` — each device runs the full local
        procedure for its slice of the flattened run×client batch."""
        fed = self.fed
        b = len(plans)
        if not fed.use_pool:
            params, _ = self.train_scanned_batched(m_in, plans, fed.e_local,
                                                   arrays=arrays, mesh=mesh)
            return params, None, [[] for _ in range(b)]
        if arrays is None:
            arrays = stack_plan_arrays(plans)
        idx = stack_plan_indices(plans, fed.pool_size * fed.e_local)
        idx = idx.reshape(b, fed.pool_size, fed.e_local, -1)
        fn = (sharded_program(self.vm_scanned_local, mesh, (True,) * 5)
              if can_shard_flat(mesh, b) else self.batched_scanned_local)
        avg, pools, tasks = fn(m_in, arrays, idx, alphas, betas)
        return avg, pools, _model_records(tasks.T, b)


def _model_records(task_grid: jax.Array, b: int) -> List[List[ModelRecord]]:
    """(S, B) last-step task losses → per-run ModelRecord lists, converted
    to host floats in one transfer."""
    grid = np.asarray(task_grid)
    return [[ModelRecord(index=j, task_loss=float(grid[j, i]))
             for j in range(grid.shape[0])] for i in range(b)]


def stack_trees(trees: List[PyTree]) -> PyTree:
    """Stack a list of structurally-identical pytrees along a new leading
    run axis. Mismatched leaf shapes raise with the offending path."""
    try:
        return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)
    except (ValueError, TypeError) as e:
        raise ValueError(
            "run_batch requires structurally identical pytrees across the "
            f"batch (same leaves, shapes and dtypes): {e}") from e


def unstack_tree(tree: PyTree, i: int) -> PyTree:
    """Slice run `i` out of a stacked pytree (inverse of `stack_trees`)."""
    return jax.tree.map(lambda x: x[i], tree)
