"""Driver-hook contract tests for ``__graft_entry__``.

The multichip dryrun is the mesh executor's correctness signal without
an accelerator (SURVEY.md §7 step 6): it runs in a subprocess held to
``n`` virtual CPU devices, and the calling process must stay off JAX —
the CPU device count is fixed before JAX starts, and on a machine with
a chip a process that touched JAX holds that chip.
"""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import __graft_entry__ as G  # noqa: E402


class _PoisonedModule:
    """Stands in for ``jax`` in sys.modules: ANY attribute access (devices,
    device_count, default_backend, jit, ...) fails loudly, so any use of
    any jax API on the calling-process path is caught."""

    def __getattr__(self, name):  # pragma: no cover - must never run
        raise AssertionError(
            f"dryrun_multichip touched jax.{name} in the calling process "
            "— that process would take the chip where there is one"
        )


def test_dryrun_leaves_the_calling_process_off_jax(monkeypatch):
    """The whole jax module is poisoned in the calling process. The
    dryrun must complete anyway via the CPU subprocess (which imports
    its own, real jax) — and that subprocess is steered by the
    environment alone: ``JAX_PLATFORMS=cpu`` and the forced device
    count, no ``jax.config`` override in its code."""
    import subprocess

    seen = {}
    real_run = subprocess.run

    def spy_run(cmd, **kw):
        seen["code"], seen["env"] = cmd[-1], kw["env"]
        return real_run(cmd, **kw)

    monkeypatch.setattr(subprocess, "run", spy_run)
    monkeypatch.setitem(sys.modules, "jax", _PoisonedModule())
    monkeypatch.delenv("PRESTO_TPU_DRYRUN_INPROC", raising=False)
    G.dryrun_multichip(2)
    assert seen["env"]["JAX_PLATFORMS"] == "cpu"
    assert (
        "--xla_force_host_platform_device_count=2"
        in seen["env"]["XLA_FLAGS"].split()
    )
    assert "jax.config" not in seen["code"]


def test_dryrun_inproc_escape_hatch(monkeypatch):
    """PRESTO_TPU_DRYRUN_INPROC=1 runs the body in-process (for runtimes
    that really do expose >= n devices — here the 8-CPU test mesh)."""
    monkeypatch.setenv("PRESTO_TPU_DRYRUN_INPROC", "1")
    G.dryrun_multichip(2)


def test_dryrun_subprocess_failure_surfaces(monkeypatch):
    """A failing subprocess must raise with its stderr, not pass silently."""
    monkeypatch.delenv("PRESTO_TPU_DRYRUN_INPROC", raising=False)
    import subprocess

    real_run = subprocess.run

    def fake_run(*a, **k):
        cp = real_run(
            [sys.executable, "-c", "import sys; sys.exit(3)"],
            capture_output=True,
            text=True,
        )
        return cp

    monkeypatch.setattr(subprocess, "run", fake_run)
    with pytest.raises(RuntimeError, match="rc=3"):
        G.dryrun_multichip(2)
