"""`attn_ms.train`: the device time of the attention scopes (`attn.kernel`,
`attn.jnp`) inside `step.task`, read through `bench/scopes.py` on the
stretch of a TPU v5e trace that `test_bench_scopes.py` records, and on a
splash kernel's custom call as the compiled program prints it."""
import os
import re
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src"),
                os.path.dirname(os.path.abspath(__file__))]

from bench import harness, scopes  # noqa: E402
from test_bench_scopes import (SCOPES, _rec, driver,  # noqa: E402,F401
                               recorded, tab)

ATTN_MS = "attn_ms.train"
# A splash kernel's custom call as the compiled program prints it: on one
# line, its op_name in the task's backward, inside the kernel's scope.
SPLASH = ('  %splash_mha_dkv_no_residuals.2 = (f32[512,128]{1,0:T(8,128)}, '
          'bf16[8,2048,128]{2,1,0:T(8,128)(2,1)}) custom-call(%p.1, %p.2), '
          'custom_call_target="tpu_custom_call", '
          'frontend_attributes={kernel_metadata={}}, '
          'metadata={op_name="jit(core)/while/body/transpose(jvp(step.task))'
          '/attn.kernel/vmap(jit(_splash_attention))/pallas_call"}')


def _programs(monkeypatch, texts):
    monkeypatch.setattr(scopes, "programs", lambda drv: texts)
    return types.SimpleNamespace(exp=object())


def test_attn_metric_reads_the_recorded_stretch(recorded, tab, monkeypatch):
    """With every task op of the recorded programs named as the scan's,
    the attention's time is the task's, op for op."""
    named = [re.sub(r"(step\.task\)*)/", r"\1/attn.jnp/", line)
             for line in recorded["hlo"]]
    rec = _rec(recorded, _programs(monkeypatch, ["\n".join(named)]))
    value = harness.load_module("metrics", ATTN_MS).read(rec)
    sp = scopes.split(rec["window"]["trace"]["events"], tab)
    steps = rec["window"]["work"] / rec["traffic"]["batch"]
    assert value > 0
    assert value == pytest.approx(1e3 * sp["step.task"] / steps, rel=1e-9)


def test_attn_and_task_metrics_read_a_splash_kernel_call(recorded,
                                                          monkeypatch):
    """A kernel's time counts in the attention and, as the split's
    outermost name, in the task."""
    event = SPLASH.strip()
    rec = _rec(recorded, _programs(monkeypatch, [SPLASH]))
    rec["window"]["trace"] = {"events": [[event, 0, 2_000_000]],
                              "busy_s": 2e-3, "window_s": 2e-3}
    steps = rec["window"]["work"] / rec["traffic"]["batch"]
    value = harness.load_module("metrics", ATTN_MS).read(rec)
    assert value == pytest.approx(2.0 / steps, rel=1e-9)
    task = scopes.split(rec["window"]["trace"]["events"],
                        scopes.table([SPLASH], SCOPES))
    assert task == {"step.task": pytest.approx(2e-3, rel=1e-9)}


@pytest.mark.parametrize("with_trace", [True, False],
                         ids=["names_neither_scope", "no_trace"])
def test_attn_metric_is_left_out(with_trace, recorded, driver):
    """The recorded programs (from before the attention's names) name
    neither scope; without a trace there is nothing to read."""
    rec = _rec(recorded, driver, with_trace=with_trace)
    assert harness.load_module("metrics", ATTN_MS).read(rec) is None
