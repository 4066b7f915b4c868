"""The local phase's names (`repro.obs`): device scopes in the compiled
step bodies, host spans around the plan interpreter's visits.

1. Each compiled path carries, in its ops' ``op_name`` metadata, the scope
   names of the parts it runs, and none of those it does not run.
2. A `launch` under the profiler writes `repro.launch`, `repro.warmup`,
   `repro.visit` (with `rank` and `client`) and, inside each visit,
   `repro.take`, `repro.dispatch` and `repro.sync`.
"""
import dataclasses
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.api import Experiment, LocalTrainer, launch
from repro.configs import FedConfig, get_arch
from repro.core import ModelPool
from repro.data import DataPlan, make_lm_dataset
from repro.models import build_model

KEY = jax.random.PRNGKey(0)
FED = FedConfig(n_clients=2, pool_size=2, e_local=2, e_warmup=2,
                learning_rate=1e-3)
STEP = {obs.TASK, obs.OPT}
LOCAL = set(obs.SCOPES)


def _cnn():
    cfg = dataclasses.replace(get_arch("paper-cnn"), d_model=4, d_ff=32)
    model = build_model(cfg)
    rng = np.random.default_rng(0)
    data = {"images": rng.normal(size=(16, 32, 32, 3)).astype(np.float32),
            "labels": np.arange(16) % 10}
    return model, data, FED


def _decoder():
    cfg = get_arch("granite-8b").reduced()
    model = build_model(cfg)
    d = make_lm_dataset(n_seqs=8, seq_len=16, vocab=cfg.vocab_size,
                        n_domains=1, seed=0)[0]
    data = {"tokens": d.tokens[:, :-1], "labels": d.tokens[:, 1:]}
    fed = dataclasses.replace(FED, pool_backend="lowrank", pool_rank=2)
    return model, data, fed


def _scopes_in(lowered) -> set:
    text = lowered.as_text(debug_info=True)
    return {s for s in obs.SCOPES if s in text}


def _paths(make):
    """(path name, lowered program, scopes it runs) for one model."""
    model, data, fed = make()
    tr = LocalTrainer(model.loss_fn, fed)
    params = model.init(KEY)
    arrays = {k: jnp.asarray(v) for k, v in data.items()}
    b = 4
    idx_local = jnp.zeros((fed.pool_size, fed.e_local, b), jnp.int32)
    batch = {k: v[:b] for k, v in arrays.items()}
    opt_state = tr.opt.init(params)
    pool = tr.backend.create(params, fed)
    return [
        ("scanned_local", tr.scanned_local.lower(
            params, arrays, idx_local, jnp.float32(fed.alpha),
            jnp.float32(fed.beta)), LOCAL),
        ("scanned_plain", tr.scanned_plain.lower(
            params, arrays, jnp.zeros((2, b), jnp.int32)), STEP),
        ("pool_step", tr.pool_step.lower(
            params, opt_state, batch, pool, jnp.int32(0)),
         STEP | {obs.REG}),
        ("plain_step", tr.plain_step.lower(
            params, opt_state, batch, jnp.int32(0)), STEP),
    ]


@pytest.mark.parametrize("path", ["scanned_local", "scanned_plain",
                                  "pool_step", "plain_step"])
@pytest.mark.parametrize("make", [_cnn, _decoder], ids=["cnn_stacked",
                                                        "decoder_lowrank"])
def test_compiled_paths_carry_their_scopes(make, path):
    ((lowered, want),) = [(lw, w) for n, lw, w in _paths(make) if n == path]
    assert _scopes_in(lowered) == want


def test_backward_ops_keep_the_forward_scope():
    """The task loss's backward is named transpose(jvp(step.task)), so the
    scope covers the forward and its backward."""
    model, data, fed = _cnn()
    tr = LocalTrainer(model.loss_fn, fed)
    params = model.init(KEY)
    batch = {k: jnp.asarray(v[:4]) for k, v in data.items()}
    text = tr.plain_step.lower(params, tr.opt.init(params), batch,
                               jnp.int32(0)).as_text(debug_info=True)
    assert f"transpose(jvp({obs.TASK}))" in text


def test_batched_pool_ops_carry_pool_scopes():
    from repro.api import trainer as T
    params = {"w": jnp.ones((2, 3, 3))}
    pools = jax.vmap(lambda m: ModelPool.create(m, capacity=3))(params)
    assert _scopes_in(T._batched_pool_average.lower(pools)) == {
        obs.POOL_AVERAGE}
    assert _scopes_in(T._batched_pool_append.lower(pools, params)) == {
        obs.POOL_APPEND}


# ---------------------------------------------------------------------------
# Host spans
# ---------------------------------------------------------------------------

def _host_spans(tdir):
    files = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"),
                      recursive=True)
    assert len(files) == 1
    pd = jax.profiler.ProfileData.from_file(files[0])
    out = []
    for plane in pd.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(obs.SPAN_PREFIX):
                    out.append((e.name[len(obs.SPAN_PREFIX):], e.start_ns,
                                e.start_ns + e.duration_ns,
                                {k: v for k, v in e.stats}))
    return out


def _inside(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


@pytest.fixture(scope="module")
def traced_launch(tmp_path_factory):
    model, data, fed = _cnn()
    plans = [DataPlan(data, 4, seed=i) for i in range(fed.n_clients)]
    exp = Experiment(model=model, client_iters=plans, fed=fed,
                     strategy="fedelmy", key=KEY)
    jax.block_until_ready(launch(exp).params)      # compile outside
    plans = [DataPlan(data, 4, seed=i) for i in range(fed.n_clients)]
    exp = dataclasses.replace(exp, client_iters=plans)
    tdir = str(tmp_path_factory.mktemp("trace"))
    jax.profiler.start_trace(tdir)
    try:
        jax.block_until_ready(launch(exp).params)
    finally:
        jax.profiler.stop_trace()
    return _host_spans(tdir), fed


def test_launch_writes_nested_spans(traced_launch):
    spans, fed = traced_launch
    by = {}
    for s in spans:
        by.setdefault(s[0], []).append(s)
    (launch_span,) = by[obs.LAUNCH]
    (warmup,) = by[obs.WARMUP]
    visits = by[obs.VISIT]
    assert [(v[3]["rank"], v[3]["client"]) for v in visits] == [
        (r, r) for r in range(fed.n_clients)]
    assert _inside(warmup, launch_span)
    assert all(_inside(v, launch_span) for v in visits)
    assert not any(_inside(warmup, v) for v in visits)
    # warmup: take and dispatch; each visit: take, dispatch, sync
    assert len(by[obs.TAKE]) == len(by[obs.DISPATCH]) == 1 + len(visits)
    assert len(by[obs.SYNC]) == len(visits)
    for v in visits:
        inner = [s[0] for s in spans if s is not v and _inside(s, v)]
        assert sorted(inner) == sorted([obs.TAKE, obs.DISPATCH, obs.SYNC])
        take, dispatch, sync = (next(s for s in spans
                                     if s[0] == n and _inside(s, v))
                                for n in (obs.TAKE, obs.DISPATCH, obs.SYNC))
        assert take[2] <= dispatch[1] and dispatch[2] <= sync[1]
