"""The strategy-plan IR: one interpreter for sequential *and* batched
federated execution.

The paper's framework is a single loop shape — a client topology, a
local-train block (plain steps, or the pool-diversity procedure with
d1/d2), and an aggregation/broadcast rule. A ``StrategyPlan`` states that
shape as *data*:

* ``Topology``   — how clients are visited: ``chain`` (one model threads
  through ``order``), ``ring`` (cycles × all clients; ``cycles="shots"``
  reads ``Experiment.shots``), or ``independent`` (clients train in
  parallel from broadcast inits).
* ``LocalBlock`` — what one visit does: ``plain`` SGD for a FedConfig
  epoch budget, the ``pool`` diversity procedure (Alg. 1 lines 3–17,
  α/β regularized), or a ``custom`` step factory (DFedSAM's SAM step,
  MetaFed's anchored penalty). A plan holds one block per *phase*; a
  phase is a full pass over the topology (MetaFed = two chain phases,
  the second anchored on the first's result).
* ``aggregate``  — ``last`` (the threaded model) or ``tree_mean``.
* ``broadcast``  — how params reach a visit: ``handoff`` (sequential),
  ``shared_init`` (same init to every client), ``per_client_init``
  (independent inits from split keys).

Two interpreter backends execute any plan:

* ``interpret(experiment, plan)`` — the sequential backend behind
  ``api.run``; replaces the eight monolithic strategy callables.
* ``interpret_batched(experiments, plan, mesh)`` — the vmapped backend
  behind ``api.run_batch``; replaces the four hand-written ``_exec_*``
  executors, and because the interpreter (not the strategy) owns the
  loop, batching extends for free to ``metafed`` (two interpreted
  passes), ``fedelmy_fewshot`` (ring cycling is topology data),
  ``fedelmy_pfl`` and ``local_only``.

Both backends call the same ``LocalTrainer`` primitives in the same
order, so per-run results are bit-identical between them and to the
pre-plan strategy bodies (pinned in tests/test_plan.py).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro import obs
from repro.api.results import ClientRecord, RoundRecord, StrategyOutput
from repro.api.trainer import LocalTrainer, stack_trees, unstack_tree
from repro.data.plan import (all_want_scan, stack_plan_arrays, wants_scan)

PyTree = Any

_TOPOLOGIES = ("chain", "ring", "independent")
_BLOCK_KINDS = ("plain", "pool", "custom")
_AGGREGATES = ("last", "tree_mean")
_BROADCASTS = ("handoff", "shared_init", "per_client_init")
_RECORDS = ("none", "clients", "clients_noeval", "rounds")


def tree_mean(trees: Sequence[PyTree]) -> PyTree:
    """Leaf-wise mean of structurally identical pytrees — the one-shot
    averaging aggregate. A running left-to-right f32 accumulation: the
    former stack-then-mean materialized N f32 copies of every leaf before
    reducing; this keeps one f32 accumulator (O(1) extra memory) and is
    deterministic in the input order. (XLA's stacked reduce reassociates
    the sum, so the two orders differ in final mantissa bits; the running
    fold is now the defining spec, pinned in tests/test_dataplan.py.)"""
    def mean_leaf(*xs):
        acc = xs[0].astype(jnp.float32)
        for x in xs[1:]:
            acc = acc + x.astype(jnp.float32)
        return (acc / len(xs)).astype(xs[0].dtype)

    return jax.tree.map(mean_leaf, *trees)


@dataclasses.dataclass(frozen=True)
class Topology:
    """Client-visit structure of one phase pass.

    kind         — "chain" | "ring" | "independent"
    honors_order — chain only: visit ``Experiment.order`` instead of
                   0..N-1 (ring/independent always use the natural order)
    cycles       — passes per phase: an int, or the string "shots" to
                   read ``Experiment.shots`` at run time (ring topology)
    """
    kind: str
    honors_order: bool = False
    cycles: Any = 1

    def __post_init__(self):
        if self.kind not in _TOPOLOGIES:
            raise ValueError(f"unknown topology kind {self.kind!r}; "
                             f"expected one of {_TOPOLOGIES}")

    def resolved_cycles(self, exp) -> int:
        return exp.shots if self.cycles == "shots" else int(self.cycles)

    def schedule(self, exp) -> List[int]:
        return (exp.resolved_order() if self.honors_order
                else list(range(len(exp.client_iters))))

    def label(self) -> str:
        if self.cycles == "shots":
            return f"{self.kind}×shots"
        if self.cycles != 1:
            return f"{self.kind}×{self.cycles}"
        return self.kind


@dataclasses.dataclass(frozen=True)
class LocalBlock:
    """What one client visit executes.

    kind     — "plain" (SGD on the task loss), "pool" (the paper's
               diversity procedure: S regularized models, pool average
               handoff), or "custom" (step factories below)
    epochs   — FedConfig field naming the step budget ("e_local")
    epochs_div — integer divisor of that budget (MetaFed: e_local // 2)
    anchored — custom only: the factory receives the params at phase
               entry (MetaFed's common model) as its anchor
    step_factory(trainer, exp, anchor) -> step_fn           — sequential
    batched_step_factory(trainer, exps, anchors) -> step_fn — vmapped;
               ``anchors`` is the stacked (B, …) phase-entry params
    label    — human name for --list / the README table
    """
    kind: str
    epochs: str = "e_local"
    epochs_div: int = 1
    anchored: bool = False
    step_factory: Optional[Callable] = None
    batched_step_factory: Optional[Callable] = None
    label: Optional[str] = None

    def __post_init__(self):
        if self.kind not in _BLOCK_KINDS:
            raise ValueError(f"unknown local block kind {self.kind!r}; "
                             f"expected one of {_BLOCK_KINDS}")
        if self.kind == "custom" and (self.step_factory is None or
                                      self.batched_step_factory is None):
            raise ValueError("custom local blocks need both step_factory "
                             "and batched_step_factory")
        if self.kind == "pool" and (self.epochs != "e_local" or
                                    self.epochs_div != 1):
            raise ValueError(
                "pool blocks train fed.e_local steps per pool model "
                "(LocalTrainer.local_client_train owns that budget); "
                "epochs/epochs_div apply to plain/custom blocks only")

    def n_steps(self, fed) -> int:
        return getattr(fed, self.epochs) // self.epochs_div

    def describe(self) -> str:
        if self.label is not None:
            return self.label
        return "pool(d1,d2)" if self.kind == "pool" else self.kind


@dataclasses.dataclass(frozen=True)
class StrategyPlan:
    """A federated strategy as declarative data, executed by the
    interpreter backends below. See the module docstring for field
    semantics; ``supports`` lists the optional Experiment fields the plan
    honors (the engine warns on the rest)."""
    topology: Topology
    phases: Tuple[LocalBlock, ...]
    aggregate: str = "last"
    broadcast: str = "handoff"
    init_from_experiment: bool = False    # honor Experiment.init_params
    warmup: Optional[str] = None          # None | "first" | "per_client"
    init_skips_warmup: bool = False       # resume: init_params ⇒ no warmup
    records: str = "none"
    keep_final_pool: bool = False
    client_selector: Optional[Callable] = None   # exp -> client indices
    trainer_overrides: Optional[Callable] = None  # fed -> LocalTrainer kw
    supports: Tuple[str, ...] = ()

    def __post_init__(self):
        if self.aggregate not in _AGGREGATES:
            raise ValueError(f"unknown aggregate {self.aggregate!r}; "
                             f"expected one of {_AGGREGATES}")
        if self.broadcast not in _BROADCASTS:
            raise ValueError(f"unknown broadcast {self.broadcast!r}; "
                             f"expected one of {_BROADCASTS}")
        if self.records not in _RECORDS:
            raise ValueError(f"unknown records policy {self.records!r}; "
                             f"expected one of {_RECORDS}")
        if not self.phases:
            raise ValueError("a plan needs at least one phase")
        if self.topology.kind == "independent":
            if len(self.phases) != 1:
                raise ValueError("independent topology is single-phase")
            if self.broadcast == "handoff":
                raise ValueError("independent topology broadcasts inits "
                                 "(shared_init or per_client_init), it "
                                 "cannot hand off sequentially")
        elif self.broadcast != "handoff":
            raise ValueError(f"{self.topology.kind} topology hands off "
                             "sequentially; broadcast must be 'handoff'")

    def describe(self) -> Dict[str, str]:
        """Plan metadata for ``--list`` and the README strategy table."""
        return {
            "topology": self.topology.label(),
            "local_block": " → ".join(b.describe() for b in self.phases),
            "aggregate": self.aggregate,
            "broadcast": self.broadcast,
            "supports": ",".join(self.supports) or "—",
        }


# ---------------------------------------------------------------------------
# Shared helpers
# ---------------------------------------------------------------------------

def _make_trainer(loss_fn: Callable, fed, plan: StrategyPlan) -> LocalTrainer:
    kw = plan.trainer_overrides(fed) if plan.trainer_overrides else {}
    return LocalTrainer(loss_fn, fed, **kw)


def _eval(exp, params) -> Optional[float]:
    if exp.eval_fn is None:
        return None
    with obs.span(obs.EVAL):
        return float(exp.eval_fn(params))


def _client_end(exp, rec, params) -> None:
    if exp.callbacks.on_client_end is not None:
        with obs.span(obs.CALLBACK):
            exp.callbacks.on_client_end(rec, params)


def _eval_slice(e, stacked: PyTree, i: int) -> Optional[float]:
    return (float(e.eval_fn(unstack_tree(stacked, i)))
            if e.eval_fn is not None else None)


def _resolved_init(exp, plan: StrategyPlan) -> PyTree:
    if plan.init_from_experiment and exp.init_params is not None:
        return exp.init_params
    return exp.model.init(exp.resolved_key())


def _wants_warmup(exp, plan: StrategyPlan) -> bool:
    if plan.warmup is None:
        return False
    if plan.init_skips_warmup and plan.init_from_experiment \
            and exp.init_params is not None:
        return False                       # resuming: warmup already ran
    return True


def _selected_clients(exp, plan: StrategyPlan) -> List[int]:
    if plan.client_selector is not None:
        return list(plan.client_selector(exp))
    return list(range(len(exp.client_iters)))


def _alphas_betas(exps, repeat: int = 1) -> Tuple[jax.Array, jax.Array]:
    return (jnp.asarray([e.fed.alpha for e in exps for _ in range(repeat)],
                        jnp.float32),
            jnp.asarray([e.fed.beta for e in exps for _ in range(repeat)],
                        jnp.float32))


def _shard(stacked: PyTree, mesh) -> PyTree:
    if mesh is not None:
        from repro.sharding.specs import shard_run_batch
        stacked = shard_run_batch(stacked, mesh)
    return stacked


# ---------------------------------------------------------------------------
# Sequential backend (behind `api.run`)
# ---------------------------------------------------------------------------

def interpret(experiment, plan: StrategyPlan) -> StrategyOutput:
    """Execute one Experiment through its plan, sequentially."""
    trainer = _make_trainer(experiment.model.loss_fn, experiment.fed, plan)
    if plan.topology.kind == "independent":
        return _interpret_independent(experiment, plan, trainer)
    return _interpret_sequenced(experiment, plan, trainer)


def _train_visit(trainer: LocalTrainer, m: PyTree, it, n_steps: int):
    """Plain training over one client stream: scan-routed DataPlans
    compile the whole visit into one scan (every model family — conv
    losses are scan-safe via kernels/local_step.py); iterators and
    scan=False plans keep the per-step loop."""
    if wants_scan(it):
        m, _ = trainer.train_scanned(m, it, n_steps)
    else:
        m, _ = trainer.train(m, it, n_steps)
    return m


def _run_block(trainer: LocalTrainer, block: LocalBlock, m: PyTree, it,
               step_fn, exp):
    """One client visit: returns (params, pool | None, model records).
    Device-resident DataPlans route through the scan-compiled phase —
    custom blocks and per-model callbacks keep the per-step iterator path
    (a DataPlan still serves it, through the same cursor)."""
    if block.kind == "pool":
        if wants_scan(it) and exp.callbacks.on_model_end is None:
            return trainer.local_client_train_scanned(m, it)
        return trainer.local_client_train(
            m, it, on_model_end=exp.callbacks.on_model_end)
    if block.kind == "plain" and wants_scan(it):
        m, _ = trainer.train_scanned(m, it, block.n_steps(trainer.fed))
        return m, None, []
    m, _ = trainer.train(m, it, block.n_steps(trainer.fed), step_fn=step_fn)
    return m, None, []


def _interpret_sequenced(exp, plan: StrategyPlan,
                         trainer: LocalTrainer) -> StrategyOutput:
    """chain / ring: one model threads through the schedule, phase by
    phase; records per client (chain) or per cycle (ring)."""
    fed = exp.fed
    schedule = plan.topology.schedule(exp)
    cycles = plan.topology.resolved_cycles(exp)
    m = _resolved_init(exp, plan)
    if _wants_warmup(exp, plan):
        with obs.span(obs.WARMUP):
            m = _train_visit(trainer, m, exp.client_iters[schedule[0]],
                             fed.e_warmup)

    clients: List[ClientRecord] = []
    rounds: List[RoundRecord] = []
    pool = None
    for block in plan.phases:
        anchor = m if block.anchored else None
        step_fn = (block.step_factory(trainer, exp, anchor)
                   if block.kind == "custom" else None)
        for r in range(cycles):
            for rank, ci in enumerate(schedule):
                with obs.span(obs.VISIT, rank=rank, client=int(ci)):
                    if block.kind == "pool":
                        m, pool, models = _run_block(trainer, block, m,
                                                     exp.client_iters[ci],
                                                     None, exp)
                    else:
                        m, _, models = _run_block(trainer, block, m,
                                                  exp.client_iters[ci],
                                                  step_fn, exp)
                    if plan.records == "clients":
                        rec = ClientRecord(client=int(ci), rank=rank,
                                           models=models,
                                           global_metric=_eval(exp, m))
                        clients.append(rec)
                        _client_end(exp, rec, m)
            if plan.records == "rounds":
                rec = RoundRecord(round=r, global_metric=_eval(exp, m))
                rounds.append(rec)
                _client_end(exp, rec, m)
    return StrategyOutput(params=m, clients=clients, rounds=rounds,
                          final_pool=pool if plan.keep_final_pool else None)


def _interpret_independent(exp, plan: StrategyPlan,
                           trainer: LocalTrainer) -> StrategyOutput:
    """independent: selected clients train in parallel (sequentially
    simulated) from broadcast inits, then aggregate."""
    fed = exp.fed
    sel = _selected_clients(exp, plan)
    if plan.broadcast == "per_client_init":
        keys = jax.random.split(exp.resolved_key(), len(exp.client_iters))
        inits = [exp.model.init(keys[c]) for c in sel]
    else:
        # shared_init honors Experiment.init_params (via _resolved_init)
        # when the plan opts in — the fleet driver threads the global
        # params through successive cohort rounds this way.
        m0 = _resolved_init(exp, plan)
        inits = [m0 for _ in sel]

    block = plan.phases[0]
    step_fn = (block.step_factory(trainer, exp, None)
               if block.kind == "custom" else None)
    outs: List[PyTree] = []
    clients: List[ClientRecord] = []
    pool = None
    for ci, m0 in zip(sel, inits):
        it = exp.client_iters[ci]
        if plan.warmup == "per_client":
            m0 = _train_visit(trainer, m0, it, fed.e_warmup)
        m, pool, models = _run_block(trainer, block, m0, it, step_fn, exp)
        outs.append(m)
        if plan.records == "clients_noeval":
            rec = ClientRecord(client=int(ci), rank=int(ci), models=models)
            clients.append(rec)
            _client_end(exp, rec, m)
    params = tree_mean(outs) if plan.aggregate == "tree_mean" else outs[-1]
    # Like the sequenced interpreter, "final pool" means the last visited
    # client's pool — the one whose diversity state is freshest.
    return StrategyOutput(params=params, clients=clients,
                          final_pool=pool if plan.keep_final_pool else None)


# ---------------------------------------------------------------------------
# Vmapped backend (behind `api.run_batch`)
# ---------------------------------------------------------------------------

def interpret_batched(exps: List[Any], plan: StrategyPlan,
                      mesh=None) -> List[StrategyOutput]:
    """Execute a compiled group of Experiments through its plan with
    stacked run axes. Per-run results are bit-identical to `interpret`
    on the same Experiment: the batched steps are the sequential step
    graphs under vmap, consuming each run's iterators in the same order.
    """
    trainer = _make_trainer(exps[0].model.loss_fn, exps[0].fed, plan)
    if plan.topology.kind == "independent":
        return _interpret_independent_batched(exps, plan, trainer, mesh)
    return _interpret_sequenced_batched(exps, plan, trainer, mesh)


def _stacked_inits(exps, plan: StrategyPlan, mesh) -> PyTree:
    return _shard(stack_trees([_resolved_init(e, plan) for e in exps]), mesh)


class _StackedArrays:
    """Per-interpretation cache of stacked (and zero-padded) DataPlan
    arrays: a chain revisits the same B plans once per cycle and phase —
    stack once, reuse the device buffer for every visit. Every stack pads
    to the longest shard among the group's *visited* streams, not the
    visit's own: one padded shape means the whole-phase scanned programs
    compile ONCE per group even when client ranks carry different shard
    lengths (quantity skew), instead of once per distinct (B, n, …)
    shape."""

    def __init__(self, streams):
        self._cache: Dict[tuple, PyTree] = {}
        ns = [it.n for it in streams if wants_scan(it)]
        self._pad_to = max(ns) if ns else None

    def get(self, plans) -> PyTree:
        key = tuple(id(p) for p in plans)
        if key not in self._cache:
            self._cache[key] = stack_plan_arrays(plans,
                                                 pad_to=self._pad_to)
        return self._cache[key]


def _batched_visit(trainer: LocalTrainer, m: PyTree, its, n_steps: int,
                   stacks: _StackedArrays, step_fn=None,
                   mesh=None) -> PyTree:
    """One batched plain/custom visit: all-DataPlan groups run the whole
    visit as one vmapped scan (stacked index tensors, no per-step host
    stack_trees re-upload); anything else keeps the per-step loop. With
    `mesh`, the program goes under shard_map across the mesh data axes
    (each device advances its slice of the flattened batch)."""
    if step_fn is None and all_want_scan(its):
        m, _ = trainer.train_scanned_batched(m, its, n_steps,
                                             arrays=stacks.get(its),
                                             mesh=mesh)
    else:
        m, _ = trainer.train_batched(m, its, n_steps, step_fn=step_fn,
                                     mesh=mesh)
    return m


def _batched_pool_visit(trainer: LocalTrainer, m: PyTree, its,
                        alphas, betas, stacks: _StackedArrays, mesh=None):
    if all_want_scan(its):
        return trainer.local_client_train_scanned_batched(
            m, its, alphas, betas, arrays=stacks.get(its), mesh=mesh)
    return trainer.local_client_train_batched(m, its, alphas, betas,
                                              mesh=mesh)


def _interpret_sequenced_batched(exps, plan: StrategyPlan,
                                 trainer: LocalTrainer,
                                 mesh) -> List[StrategyOutput]:
    fed = exps[0].fed
    schedules = [plan.topology.schedule(e) for e in exps]
    cycles = plan.topology.resolved_cycles(exps[0])
    alphas, betas = _alphas_betas(exps)
    stacks = _StackedArrays([e.client_iters[ci]
                             for e, s in zip(exps, schedules)
                             for ci in s])
    m = _stacked_inits(exps, plan, mesh)
    if _wants_warmup(exps[0], plan):
        warm = [e.client_iters[s[0]] for e, s in zip(exps, schedules)]
        m = _batched_visit(trainer, m, warm, fed.e_warmup, stacks)

    clients: List[List[ClientRecord]] = [[] for _ in exps]
    rounds: List[List[RoundRecord]] = [[] for _ in exps]
    pools = None
    for block in plan.phases:
        anchors = m if block.anchored else None
        step_fn = (block.batched_step_factory(trainer, exps, anchors)
                   if block.kind == "custom" else None)
        for r in range(cycles):
            for rank in range(len(schedules[0])):
                its = [e.client_iters[s[rank]]
                       for e, s in zip(exps, schedules)]
                if block.kind == "pool":
                    m, pools, recs = _batched_pool_visit(
                        trainer, m, its, alphas, betas, stacks)
                else:
                    m = _batched_visit(trainer, m, its, block.n_steps(fed),
                                       stacks, step_fn=step_fn)
                    recs = [[] for _ in exps]
                if plan.records == "clients":
                    for i, e in enumerate(exps):
                        clients[i].append(ClientRecord(
                            client=int(schedules[i][rank]), rank=rank,
                            models=recs[i],
                            global_metric=_eval_slice(e, m, i)))
            if plan.records == "rounds":
                for i, e in enumerate(exps):
                    rounds[i].append(RoundRecord(
                        round=r, global_metric=_eval_slice(e, m, i)))
    return [StrategyOutput(
                params=unstack_tree(m, i), clients=clients[i],
                rounds=rounds[i],
                final_pool=(unstack_tree(pools, i)
                            if plan.keep_final_pool and pools is not None
                            else None))
            for i in range(len(exps))]


def _interpret_independent_batched(exps, plan: StrategyPlan,
                                   trainer: LocalTrainer,
                                   mesh) -> List[StrategyOutput]:
    """Clients within a run are independent, so the run and client axes
    flatten into one (B·N,) vmap axis — within-round client-parallel
    training on top of the cross-run batching. This flattened axis is the
    one the mesh shards: with a mesh whose data-axis device count divides
    B·N, every visit below runs under shard_map (one compiled program,
    each device advancing its slice of runs×clients); otherwise the
    single-program vmap path is chosen — both bit-identical."""
    fed = exps[0].fed
    sel = _selected_clients(exps[0], plan)   # group key fixes the selection
    n_sel = len(sel)
    if plan.broadcast == "per_client_init":
        inits = []
        for e in exps:
            keys = jax.random.split(e.resolved_key(), len(e.client_iters))
            inits.extend(e.model.init(keys[c]) for c in sel)
    else:
        m0s = [_resolved_init(e, plan) for e in exps]
        inits = [m0 for m0 in m0s for _ in sel]
    flat = _shard(stack_trees(inits), mesh)
    flat_iters = [e.client_iters[c] for e in exps for c in sel]
    stacks = _StackedArrays(flat_iters)
    if plan.warmup == "per_client":
        flat = _batched_visit(trainer, flat, flat_iters, fed.e_warmup,
                              stacks, mesh=mesh)

    block = plan.phases[0]
    recs: List[List[Any]] = [[] for _ in flat_iters]
    pools = None
    if block.kind == "pool":
        alphas, betas = _alphas_betas(exps, repeat=n_sel)
        flat, pools, recs = _batched_pool_visit(trainer, flat, flat_iters,
                                                alphas, betas, stacks,
                                                mesh=mesh)
    else:
        step_fn = (block.batched_step_factory(trainer, exps, None)
                   if block.kind == "custom" else None)
        flat = _batched_visit(trainer, flat, flat_iters, block.n_steps(fed),
                              stacks, step_fn=step_fn, mesh=mesh)

    outs: List[StrategyOutput] = []
    for i, e in enumerate(exps):
        slices = [unstack_tree(flat, i * n_sel + k) for k in range(n_sel)]
        clients: List[ClientRecord] = []
        if plan.records == "clients_noeval":
            clients = [ClientRecord(client=int(c), rank=int(c),
                                    models=recs[i * n_sel + k])
                       for k, c in enumerate(sel)]
        params = (tree_mean(slices) if plan.aggregate == "tree_mean"
                  else slices[-1])
        # Matches _interpret_independent: the run's final pool is its last
        # selected client's pool (flat index i*n_sel + n_sel - 1).
        pool = (unstack_tree(pools, i * n_sel + n_sel - 1)
                if plan.keep_final_pool and pools is not None else None)
        outs.append(StrategyOutput(params=params, clients=clients,
                                   final_pool=pool))
    return outs
