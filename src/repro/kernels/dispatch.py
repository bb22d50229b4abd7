"""Global switch between the jnp reference path and Pallas TPU kernels.

On TPU, enable with ``set_use_pallas(True)`` (or REPRO_USE_PALLAS=1). On
CPU the kernels run in interpret mode and are only used by the kernel
tests/benchmarks; models default to the XLA path. Interpret mode follows
the backend and nothing else: a TPU process always compiles the kernels.
"""
from __future__ import annotations

import os
from contextlib import contextmanager
from typing import Optional

_USE_PALLAS = os.environ.get("REPRO_USE_PALLAS", "0") == "1"


def use_pallas() -> bool:
    return _USE_PALLAS


def set_use_pallas(v: bool) -> None:
    global _USE_PALLAS
    _USE_PALLAS = bool(v)


def interpret_mode() -> bool:
    """Interpret everywhere except on a TPU backend."""
    import jax
    return jax.default_backend() != "tpu"


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """A kernel entry point's ``interpret`` argument: ``None`` (the
    default) follows :func:`interpret_mode`."""
    return interpret_mode() if interpret is None else bool(interpret)


@contextmanager
def pallas_enabled(v: bool = True):
    old = use_pallas()
    set_use_pallas(v)
    try:
        yield
    finally:
        set_use_pallas(old)
