"""Straggler monitor + elastic re-mesh planning."""

import pytest

from repro.runtime import ElasticPlan, StepMonitor, StragglerPolicy, plan_remesh


def test_straggler_flagging_with_synthetic_clock():
    mon = StepMonitor(StragglerPolicy(window=16, threshold=3.0, min_samples=3,
                                      grace_seconds=0.0))
    t = 0.0
    for i in range(5):  # five 1-second units establish the median
        mon.start(f"u{i}", now=t)
        mon.finish(f"u{i}", now=t + 1.0)
        t += 1.0
    mon.start("slow", now=t)
    assert mon.check_stragglers(now=t + 2.0) == []  # under 3x median
    assert mon.check_stragglers(now=t + 3.5) == ["slow"]
    assert "slow" in mon.flagged


def test_no_flags_before_min_samples():
    mon = StepMonitor(StragglerPolicy(min_samples=5, grace_seconds=0.0))
    mon.start("a", now=0.0)
    mon.finish("a", now=1.0)
    mon.start("b", now=1.0)
    assert mon.check_stragglers(now=100.0) == []


def test_monitor_median():
    mon = StepMonitor(StragglerPolicy(min_samples=3))
    for i, dur in enumerate([1.0, 5.0, 2.0]):
        mon.start(f"u{i}", now=0.0)
        mon.finish(f"u{i}", now=dur)
    assert mon.median() == 2.0


def test_plan_remesh_node_loss():
    old = ElasticPlan(data=16, model=16, pods=1, grad_accum=1)
    # lose 16 devices: 240 healthy -> best grid with model divisor 16 is 15x16
    plan = plan_remesh(240, model_divisors=(16, 8, 4), target_global_batch=256, old_plan=old)
    assert plan.model == 16 and plan.data == 15
    assert plan.devices == 240
    assert plan.grad_accum >= 2  # keeps global batch via accumulation


def test_plan_remesh_prefers_larger_model_axis_on_tie():
    old = ElasticPlan(data=4, model=4, pods=1, grad_accum=1)
    plan = plan_remesh(16, model_divisors=(8, 4, 2), target_global_batch=64, old_plan=old)
    assert plan.devices == 16
    assert plan.model == 8


def test_plan_remesh_impossible_raises():
    old = ElasticPlan(data=1, model=1, pods=1, grad_accum=1)
    with pytest.raises(ValueError):
        plan_remesh(1, model_divisors=(8,), target_global_batch=8, old_plan=old)



@pytest.mark.parametrize("modules,absent", [
    ("repro.runtime, repro.runtime.cluster", "repro.core."),
    ("repro.core.regions, repro.data", "repro.core.executor"),
])
def test_imports_leave_the_executor_out(modules, absent):
    # repro.core.executor imports repro.runtime and repro.data; if importing
    # those (or one core submodule) imported the executor back, a thread
    # importing one end of the cycle while another imports the other could
    # be handed a partly initialized module
    import subprocess
    import sys

    code = (f"import sys, {modules}; "
            f"print(sorted(m for m in sys.modules if m.startswith({absent!r})))")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True,
                         timeout=120, check=True)
    assert out.stdout.strip() == "[]", out.stdout + out.stderr
