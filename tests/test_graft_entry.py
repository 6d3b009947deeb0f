"""Guardrail for the driver entry points: the jittable forward step
and the multi-chip dry run must keep compiling and executing on the
virtual mesh exactly as the driver invokes them."""

import jax
import pytest

import __graft_entry__ as graft


def test_entry_compiles_single_device():
    fn, args = graft.entry()
    out = jax.jit(fn)(*args)
    logits, value = out
    assert logits.shape[0] == args[1].shape[0]
    assert value.shape[0] == args[1].shape[0]


@pytest.mark.slow
def test_dryrun_multichip_8():
    graft.dryrun_multichip(8)


@pytest.mark.slow
def test_dryrun_multichip_odd():
    # No even split: the 2-D data x time phase is skipped but the DP
    # PPO step must still run.
    graft.dryrun_multichip(1)


def test_dryrun_dispatches_to_subprocess_when_short_on_devices(monkeypatch):
    # Driver scenario: ambient backend exposes fewer devices than
    # requested -> the virtual-mesh subprocess leg must be taken.
    calls = []
    monkeypatch.setattr(jax, "devices", lambda *a, **k: [object()])
    monkeypatch.setattr(
        graft, "_dryrun_in_virtual_subprocess", lambda n: calls.append(n)
    )
    graft.dryrun_multichip(8)
    assert calls == [8]


def test_dryrun_raises_on_backend_boot_failure(monkeypatch):
    # A backend that fails to boot is an error, not a reason to run
    # somewhere else: the CPU subprocess must NOT be taken.
    calls = []

    def boom(*a, **k):
        raise RuntimeError("Unable to initialize backend 'tpu'")

    monkeypatch.setattr(jax, "devices", boom)
    monkeypatch.setattr(
        graft, "_dryrun_in_virtual_subprocess", lambda n: calls.append(n)
    )
    with pytest.raises(RuntimeError, match="Unable to initialize backend"):
        graft.dryrun_multichip(8)
    assert calls == []


@pytest.mark.slow
def test_dryrun_subprocess_leg_end_to_end():
    # Exercise the real subprocess + --virtual-dryrun __main__ protocol
    # (the conftest mesh has 8 devices, so any n <= 8 would run
    # in-process; call the subprocess leg directly with a small n).
    graft._dryrun_in_virtual_subprocess(2)
