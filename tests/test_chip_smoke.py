"""chip_smoke.py rehearsed in-process on the CPU at tiny sizes.

The smoke is the quickest proof that the scheduling path still starts on
the chip; these tests keep ITS control flow honest without one: the phases
run through the normal entry points, the last stdout line has the
contract's shape, a device that is not a TPU is never "ok", and a dispatch
the host answered in the device's place turns the run not-ok with the
underlying exception text.  Sizes: 256 nodes / 2048 pods — the smallest
backlog whose fast batches reach ``fast_device_min`` (1024), so the
resident kernel really dispatches.
"""

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import chip_smoke  # noqa: E402

TINY = ["--nodes", "256", "--pods", "2048"]


def _last_line_doc(out: str) -> dict:
    doc = json.loads(out.strip().splitlines()[-1])
    # exactly the contract's keys — the driver reads these, nothing else
    # belongs in that line
    assert set(doc) == {"ok", "device"}
    assert set(doc["device"]) == {"platform", "kind", "count"}
    return doc


def test_rehearsal_runs_every_phase_and_is_never_ok(capsys):
    import jax

    rc = chip_smoke.main(TINY)
    out = capsys.readouterr().out
    doc = _last_line_doc(out)
    assert rc != 0 and doc["ok"] is False
    assert doc["device"] == {
        "platform": "cpu",
        "kind": jax.devices()[0].device_kind,
        "count": len(jax.devices()),
    }
    for phase in ("device", "drain", "constraints", "identity"):
        assert f"--- phase {phase} ---" in out, phase
    assert "--- phase mesh ---" not in out and "--- phase served ---" not in out
    # the ONLY thing wrong with a clean rehearsal is the device itself:
    # every phase bound all its pods, zero diffs, zero breaker failures,
    # the kernels each phase exists to exercise dispatched, and the
    # second drain compiled nothing
    fails = [ln for ln in out.splitlines() if ln.startswith("[smoke] FAIL")]
    assert len(fails) == 1 and "is not a TPU" in fails[0], fails
    assert "second drain bound 2048" in out and "(0 compiles)" in out
    assert "'resident.resident_run'" in out and '"wave.wave_run": 1' in out
    assert out.count('"diffs": 0') == 3


def test_no_accelerator_and_no_rehearsal_sizes_fails_at_once(capsys):
    """As the driver first runs it in the sandbox: no arguments, no chip →
    non-zero exit, no "ok": true, and no phase wasted on a CPU."""
    rc = chip_smoke.main([])
    out = capsys.readouterr().out
    assert rc != 0
    assert _last_line_doc(out)["ok"] is False
    assert "--- phase drain ---" not in out


def test_forced_breaker_failure_turns_the_smoke_not_ok(capsys):
    """Every dispatch raises (chaos.device injector, rate 1.0): the
    fallback engines still bind every pod bit-identically — which is
    exactly why the smoke must call the run not-ok, and say what the
    device said."""
    from kubernetes_tpu.chaos import device as chaos_device
    from kubernetes_tpu.chaos.faults import FaultPlan

    inj = chaos_device.DeviceFaultInjector(
        FaultPlan(seed=7, rates={"dispatch_error": 1.0})
    )
    chaos_device.install(inj)
    try:
        with chip_smoke.Smoke(256, 2048) as smoke:
            smoke.drain()
    finally:
        chaos_device.install(None)
    out = capsys.readouterr().out
    assert "first drain bound 2048" in out  # the host answered, silently
    assert any("__breaker" in f for f in smoke.failures), smoke.failures
    # the first underlying exception text, not just a count
    assert any("chaos dispatch_error" in f for f in smoke.failures), (
        smoke.failures
    )


def test_private_jax_attributes_the_ledger_relies_on():
    """``PjitFunction._cache_size`` is private JAX API: the ledger's
    compile classification (observability/kernels.py) and the sanitizer's
    jit-root discovery + retrace sweep (analysis/sanitizer.py) both read
    it.  If a JAX upgrade drops or changes it, THIS test fails by name —
    instead of every ledger-wrapped dispatch in the suite."""
    import jax
    import jax.numpy as jnp

    fn = jax.jit(lambda x: x + 1)
    assert callable(getattr(fn, "_cache_size", None)), (
        "jax.jit functions lost _cache_size — DispatchLedger."
        "_record_dispatch and sanitizer._discover_jit_roots need a "
        "replacement"
    )
    assert fn._cache_size() == 0
    fn(jnp.zeros((2,), jnp.int32))
    assert fn._cache_size() == 1
    fn(jnp.zeros((3,), jnp.int32))  # new shape → new executable
    assert fn._cache_size() == 2
    # the public marker the ledger's in-trace gate uses
    assert isinstance(jax.core.Tracer, type)
