"""Production mesh builders.

Functions (not module-level constants) so importing this module never touches
jax device state — the dry-run must set XLA_FLAGS before any jax init.
"""
from __future__ import annotations

from repro.compat import make_mesh as _mk


def make_production_mesh(*, multi_pod: bool = False):
    """v5e pod: 16x16 = 256 chips; multi-pod: 2 pods = 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mk(shape, axes)


def make_tiny_mesh(*, multi_pod: bool = False):
    """8-device mesh for CPU integration tests (same axis names)."""
    shape = (2, 2, 2) if multi_pod else (2, 4)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mk(shape, axes)


def local_mesh_shape(n: int):
    """(data, model) for ``n`` devices: the model axis is the largest divisor
    of ``n`` that is at most its square root — one chip (1, 1), a 2x2 host
    (2, 2), eight devices (4, 2)."""
    model = max(d for d in range(1, int(n ** 0.5) + 1) if n % d == 0)
    return n // model, model


def make_local_mesh(devices=None):
    """(data, model) mesh over ``devices`` (default: every local device)."""
    import jax
    devices = list(jax.local_devices() if devices is None else devices)
    return _mk(local_mesh_shape(len(devices)), ("data", "model"),
               devices=devices)
