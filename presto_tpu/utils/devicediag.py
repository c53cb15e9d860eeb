"""Structured device-backend diagnosis.

A backend that fails to come up leaves one opaque line unless the init
path is split: which phase died (device enumeration? XLA compile? the
first real dispatch?) and what the error class was are otherwise
unrecoverable from a record. This module runs the init path as three
separately-attributed phases and records the outcome as data:

- ``enumerate`` — ``jax.devices()``: the runtime loads and reports
  devices;
- ``compile`` — a tiny jit program lowers and compiles: the XLA
  toolchain behind the device answers;
- ``execute`` — the compiled program runs and its result fetches
  correctly: enumeration alone does not prove a dispatch works.

The resulting :class:`BackendDiag` is surfaced on ``/v1/status``, in
``system.runtime.nodes``, and as a ``backend_diag`` object on every
bench line. A failed probe is the embedder's to raise on: nothing in
this tree falls back to another backend.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from typing import Optional

from presto_tpu.utils.metrics import REGISTRY


@dataclasses.dataclass
class BackendDiag:
    """One probe's structured outcome."""

    backend: str = ""  # platform actually probed ("" = none came up)
    #: first failing phase (enumerate|compile|execute), or "ok"
    phase: str = "ok"
    ok: bool = True
    error_class: str = ""
    error: str = ""
    device_count: int = 0
    probed_at: float = 0.0

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)


_LOCK = threading.Lock()
_LAST: Optional[BackendDiag] = None


def record_diag(diag: BackendDiag) -> BackendDiag:
    """Install ``diag`` as the process's last probe outcome."""
    global _LAST
    REGISTRY.counter("device.probes").update()
    if not diag.ok:
        REGISTRY.counter("device.probe_failures").update()
    with _LOCK:
        _LAST = diag
    return diag


def last_diag() -> Optional[BackendDiag]:
    with _LOCK:
        return _LAST


def last_diag_dict() -> dict:
    """The last probe as a plain dict ({} = never probed) — the shape
    status endpoints and bench lines attach."""
    d = last_diag()
    return d.to_dict() if d is not None else {}


def probe_backend(platform: Optional[str] = None) -> BackendDiag:
    """Run the three-phase init probe and record the outcome.

    Never raises: a dead backend returns a diag with ``ok=False`` and
    the failing phase — the caller raises on it."""
    diag = BackendDiag(probed_at=time.time())
    phase = "enumerate"
    try:
        import jax
        import jax.numpy as jnp

        devs = jax.devices(platform) if platform else jax.devices()
        diag.device_count = len(devs)
        diag.backend = devs[0].platform if devs else ""

        phase = "compile"
        x = jnp.arange(4)
        runnable = jax.jit(lambda v: v + 1).lower(x).compile()

        phase = "execute"
        out = jax.device_get(runnable(x))
        if int(out.sum()) != 10:
            raise RuntimeError("backend computed a wrong result")
        diag.phase = "ok"
    except Exception as e:
        diag.ok = False
        diag.phase = phase
        diag.error_class = type(e).__name__
        diag.error = str(e)[:300]
    return record_diag(diag)
