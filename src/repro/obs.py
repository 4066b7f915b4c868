"""Names for the local phase's work, on the profiler's own clock.

Two kinds of name, both read from a `jax.profiler` trace:

* device scopes: `jax.named_scope(<name>)` where the op is made — the task
  loss and the regularizer in `api/trainer.py`, the optimizer's `update`
  (`optim/optimizers.py`), the pools' `create`, `average` and `append`
  (`core/pool.py`). They are metadata written while tracing — each op's
  ``op_name`` path — so they cost nothing at run time and leave the
  compiled operations as they were (only a Pallas kernel's custom call
  takes its instruction name from them). A backward op keeps its forward's
  name under ``transpose(jvp(...))``, so a scope covers its forward and its
  backward. A fused op carries its root's ``op_name``. An op belongs to the
  outermost of these names in its path.
* host spans (`span`): `jax.profiler.TraceAnnotation`s named
  ``repro.<name>`` around what the plan interpreter does on the host in
  each client visit. While no trace is being taken a span costs a flag
  check.

There is no switch and no store: the profiler holds both while a trace is
being taken.

JAX's persistent compilation cache leaves metadata out of its key by
default, so a cached executable compiled from the same program without
these names would be handed back without them. Importing this module keys
the cache on metadata as well. The cost: a cache entry now also depends on
the source files and lines the program was traced from, so an edit that
moves a line, or a checkout at another path, compiles afresh once.
"""
from __future__ import annotations

import jax

jax.config.update("jax_compilation_cache_include_metadata_in_key", True)

# Device scopes.
TASK = "step.task"              # the task loss and its batch gather
REG = "step.reg"                # d1, d2 and their log-scale calibration
OPT = "step.opt"                # the optimizer update
POOL_CREATE = "pool.create"
POOL_AVERAGE = "pool.average"
POOL_APPEND = "pool.append"
SCOPES = (TASK, REG, OPT, POOL_CREATE, POOL_AVERAGE, POOL_APPEND)
# The decoder layers of a step that keep their projection outputs for the
# backward (`models/transformer.decoder_remat`); absent where they are
# recomputed. Not a part of the split above: it lies inside `step.task`.
REMAT_KEEP = "remat.keep_proj"
# A MoE layer's work (`models/moe.py`), also inside `step.task`: the router,
# its top-k and balance loss; the permutation of the assignments to and
# from the held experts; the held experts' grouped GEMMs; the shared
# experts.
MOE_ROUTE = "moe.route"
MOE_DISPATCH = "moe.dispatch"
MOE_EXPERTS = "moe.experts"
MOE_SHARED = "moe.shared"
MOE_SCOPES = (MOE_ROUTE, MOE_DISPATCH, MOE_EXPERTS, MOE_SHARED)
# The attention of `models/layers.flash_attention`, inside `step.task` as
# well: the TPU kernel (`kernels/ops.attention`) where the call's shapes
# allow it, else the jnp KV-block scan. The compiled step names the path
# each call took.
ATTN_KERNEL = "attn.kernel"
ATTN_JNP = "attn.jnp"
ATTN_SCOPES = (ATTN_KERNEL, ATTN_JNP)

# Host spans, each written as SPAN_PREFIX + name.
SPAN_PREFIX = "repro."
LAUNCH = "launch"               # one run of an Experiment
WARMUP = "warmup"               # the chain's warmup phase
VISIT = "visit"                 # one client visit (args: rank, client)
TAKE = "take"                   # the next schedule rows of a DataPlan
DISPATCH = "dispatch"           # the call into a compiled local phase
SYNC = "sync"                   # the host waiting for the visit's losses
EVAL = "eval"
CALLBACK = "callback"


def span(name: str, **args):
    """A host span ``repro.<name>`` with `args` as its stats."""
    return jax.profiler.TraceAnnotation(SPAN_PREFIX + name, **args)
