"""Reader ``scope_under`` (``benchmarks/readers/``) on a hand-made trace with
known answers: an op counts under a stage that lies ANYWHERE in its
``op_name``, where ``scope`` counts it under the innermost one only — which
is why ``scope`` reads 0 for ``ktpu/wave/speculation``, a stage that only
wraps the per-pod filter, score and select."""

from types import SimpleNamespace as NS

import pytest

from benchmarks.readers import scope, scope_under

SPEC, SELECT = "ktpu/wave/speculation", "ktpu/gang/select"
OPS = {  # event name -> (tf_op, start ns, duration ns)
    "%fusion.1 = s32[] fusion()": (f"jit(chain_dispatch)/{SPEC}/vmap()/{SELECT}/argmax:", 2000.0, 800.0),
    "%fusion.2 = s32[] fusion()": (f"jit(chain_dispatch)/ktpu/wave/admission/while/body/{SELECT}/argmax:", 3000.0, 500.0),
    "%gather.3 = s32[] gather()": ("jit(chain_dispatch)/ktpu/gang/precompute/gather:", 4000.0, 300.0),
    "%fusion.4 = s32[] fusion()": (f"jit(chain_dispatch)/{SPEC}/add:", 4500.0, 100.0),
}
TF_OPS = {name: tf_op for name, (tf_op, _a, _d) in OPS.items()}
WINDOW = (1000.0, 11000.0)


def _planes(module="jit_chain_dispatch(1)"):
    events = [NS(name=n, start_ns=a, duration_ns=d) for n, (_t, a, d) in OPS.items()]
    # an op with no op_name of its own, directly before fusion.1: placed with it
    events.append(NS(name="%helper.9 = s32[] fusion()", start_ns=1900.0, duration_ns=50.0))
    return [NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=[NS(name=module, start_ns=1500.0, duration_ns=4000.0)]),
        NS(name="XLA Ops", events=events),
    ])]


def test_innermost_stage_reads_nothing_for_a_stage_that_only_wraps_others():
    table = scope.by_stage(_planes(), TF_OPS, ["chain_dispatch"], WINDOW)
    assert table[SELECT] == pytest.approx((800 + 50 + 500) * 1e-9)
    assert table[SPEC] == pytest.approx(100e-9)  # only the op traced directly in it


@pytest.mark.parametrize("stage,ns", [
    (SPEC, 800 + 50 + 100),          # fusion.1 with its helper, and fusion.4
    (SELECT, 800 + 50 + 500),        # an innermost stage reads as scope reads it
    ("ktpu/wave/admission", 500),
    ("ktpu/chain/append", 0),        # a stage no op carries reads 0, not None
])
def test_seconds_under_a_stage(stage, ns):
    got = scope_under.under(_planes(), TF_OPS, stage, ["chain_dispatch"], WINDOW)
    assert got == pytest.approx(ns * 1e-9)


def test_nothing_to_read_is_none_and_an_unknown_what_raises():
    assert scope_under.under(_planes("jit_usage_checksum(2)"), TF_OPS, SPEC, ["chain_dispatch"], WINDOW) is None
    params = {"what": "stage_ms_per_kpod", "stage": SPEC, "modules": ["chain_dispatch"]}
    assert scope_under.read({"pods_in_window": 0}, params) is None
    assert scope_under.read({"pods_in_window": 10, "_xspan": None}, params) is None  # no trace
    with pytest.raises(ValueError, match="scoped_share"):
        scope_under.read({"pods_in_window": 10}, {**params, "what": "scoped_share"})
