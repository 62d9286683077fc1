"""Production meshes.

Single pod: 16x16 = 256 chips, axes ("data", "model").
Multi-pod:  2x16x16 = 512 chips, axes ("pod", "data", "model") — the "pod"
axis crosses DCN; recipes map it to extra data parallelism (or extra
sequence parallelism for long-context cells).

Defined as functions so importing this module never touches jax device
state (jax locks the device count on first use). All construction goes
through repro.compat, the one home of the mesh spellings.
"""

from __future__ import annotations

import jax

from repro import compat


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return compat.make_mesh(shape, axes)


def make_host_mesh(model: int = 1, data: int | None = None):
    """Small mesh over however many (possibly fake) local devices exist —
    used by tests and the CPU trainer."""
    n = len(jax.devices())
    data = data or max(1, n // model)
    return compat.make_mesh((data, model), ("data", "model"))
