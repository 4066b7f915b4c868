"""Device time by the names the program gives its work (`repro.obs`).

The program names the parts of its local phase with `jax.named_scope`:
`step.task`, `step.reg`, `step.opt`, `pool.create`, `pool.average`,
`pool.append`. A scope lives in each op's `op_name` metadata, and an op
belongs to the outermost of these names in its path (a backward op keeps
its forward's name under `transpose(jvp(...))`; a fused op carries its
root's `op_name`).

A TPU trace's "XLA Ops" events carry the op's HLO instruction text but
not its metadata, so the metadata comes from the compiled programs
themselves: `programs` asks the driver's Experiment for the local phase's
programs (`scanned_plain` for the warmup, `scanned_local` for a client
visit), lowers and compiles them again for the shapes they ran at (the
process compiled them before the window, so this loads what it holds),
and returns their HLO text with metadata. `table` maps each instruction
(name, result type, opcode) to its scope; `split` charges each event's
own time to its op's scope.

Against a program that names nothing, as before `repro.obs` existed,
`read_split` returns None and the metrics that read it are left out.
"""
from __future__ import annotations

import re
from typing import Dict, Iterable, List, Optional, Tuple

from bench import trace

UNSCOPED = "unscoped"

# '%fusion.12 = f32[2,3]{1,0} fusion(...' or, in HLO text, with 'ROOT '
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%([^\s=]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def _type_and_opcode(rest: str) -> Tuple[str, str]:
    """'f32[2]{0} add(...' -> ('f32[2]{0}', 'add'); a tuple type is taken
    to its closing parenthesis."""
    if rest.startswith("("):
        depth = 0
        for i, c in enumerate(rest):
            depth += (c == "(") - (c == ")")
            if depth == 0:
                break
        typ, tail = rest[:i + 1], rest[i + 1:]
    else:
        typ, _, tail = rest.partition(" ")
    return typ, tail.strip().split("(", 1)[0]


def key_of(text: str) -> Optional[Tuple[str, str, str]]:
    """(instruction name, result type, opcode) of one instruction's text,
    as an event names it or an HLO line prints it."""
    m = _INSTR.match(text)
    if not m:
        return None
    return (m.group(1),) + _type_and_opcode(text[m.end():])


def scope_of(op_name: str, scopes: Iterable[str]) -> Optional[str]:
    """The outermost of `scopes` in an `op_name` path: a path element is
    the scope itself or wraps it, as `transpose(jvp(step.task))` does."""
    names = "|".join(re.escape(s) for s in scopes)
    m = re.search(rf"(?:^|[/(])({names})(?=$|[/)])", op_name)
    return m.group(1) if m else None


def table(hlo_texts: Iterable[str], scopes: Iterable[str]
          ) -> Dict[Tuple[str, str, str], Optional[str]]:
    """Instruction key -> its scope (None: no scope, or programs that
    disagree about an instruction of that key)."""
    scopes = tuple(scopes)
    out: Dict[Tuple[str, str, str], Optional[str]] = {}
    for text in hlo_texts:
        for line in text.splitlines():
            k = key_of(line)
            if k is None:
                continue
            m = _OP_NAME.search(line)
            sc = scope_of(m.group(1), scopes) if m else None
            out[k] = sc if out.get(k, sc) == sc else None
    return out


def split(events: List[list], tab) -> Dict[str, float]:
    """Seconds of each scope's own device time in `events` ([hlo text,
    start_ns, dur_ns], nested as a trace nests them), and under UNSCOPED
    the rest: ops without a scope and ops no program holds."""
    named = [[tab.get(key_of(name)) or UNSCOPED, s, d]
             for name, s, d in events]
    own = trace.self_times(named)
    return {k: v / 1e9 for k, v in own.items()}


def programs(driver) -> List[str]:
    """The HLO text, with metadata, of the programs a launch of the
    driver's Experiment runs on each distinct client data shape."""
    import jax
    import jax.numpy as jnp
    from repro.api import LocalTrainer
    exp = driver.exp
    fed = exp.fed
    params = exp.init_params
    tr = LocalTrainer(exp.model.loss_fn, fed)

    def rows(*shape):           # as `DataPlan.take` uploads them
        return jax.ShapeDtypeStruct(shape, jnp.int32)

    scalar = jax.ShapeDtypeStruct((), jnp.float32)
    texts, seen = [], set()
    for plan in exp.client_iters:
        shape = tuple((k, v.shape) for k, v in sorted(plan.arrays.items()))
        if shape in seen:
            continue
        seen.add(shape)
        b = plan.batch_size
        calls = [(tr.scanned_local, (
            params, plan.arrays, rows(fed.pool_size, fed.e_local, b),
            scalar, scalar))]
        if fed.e_warmup:
            calls.append((tr.scanned_plain, (
                params, plan.arrays, rows(fed.e_warmup, b))))
        texts += [fn.lower(*args).compile().as_text() for fn, args in calls]
    return texts


_MEMO: Dict[int, tuple] = {}


def read_split(rec) -> Optional[Dict[str, float]]:
    """Seconds by scope over the traced window on device 0, or None where
    there is no trace or the program names no scope."""
    tr = rec["window"].get("trace")
    driver = rec.get("driver")
    if not tr or driver is None or getattr(driver, "exp", None) is None:
        return None
    try:
        from repro import obs
    except ImportError:         # a program from before the names
        return None
    memo = _MEMO.get(id(tr))    # the metrics of one run read one split
    if memo is None or memo[0] is not tr:
        tab = table(programs(driver), obs.SCOPES)
        out = split(tr["events"], tab) if any(tab.values()) else None
        _MEMO.clear()
        memo = _MEMO[id(tr)] = (tr, out)
    return memo[1]


def per_unit(rec, names: Iterable[str], units: float) -> Optional[float]:
    """Milliseconds of `names`' own device time in the window per unit of
    work (a step, a slot)."""
    sp = read_split(rec)
    if sp is None or units <= 0:
        return None
    return 1e3 * sum(sp.get(n, 0.0) for n in names) / units
