"""Kernel implementation policy: impl / interpret resolved once, from env.

Every kernel family (``delta_apply``, ``segment_sum``) exposes ``impl=``
(``"pallas"`` | ``"xla"``) and, for the Pallas path, ``interpret=``.  The
policy is resolved in one place:

* on a TPU backend the only default is the compiled Pallas kernel,
  ``("pallas", False)``; an environment override that asks for XLA or
  for interpret mode there raises instead of quietly hiding the device;
* elsewhere ``REPRO_KERNEL=pallas|xla`` pins the implementation (default
  ``xla`` — CPU has no Mosaic compiler, the XLA path *is* the fast path
  there) and ``REPRO_KERNEL_INTERPRET=0|1`` the interpret mode (default
  on: the Pallas interpreter is correct but slow, the parity suite's
  mode).

Explicit call-site values always win, on every backend.
"""
from __future__ import annotations

import os

VALID_IMPLS = ("pallas", "xla")


def backend() -> str:
    """The active JAX backend platform name (``cpu``/``tpu``/``gpu``)."""
    import jax
    return jax.default_backend()


def _env(name: str) -> str:
    return os.environ.get(name, "").strip().lower()


def default_impl() -> str:
    """Policy default when the call site pins no implementation."""
    env = _env("REPRO_KERNEL")
    if env and env not in VALID_IMPLS:
        raise ValueError(
            f"REPRO_KERNEL={env!r} invalid; choose from {VALID_IMPLS}")
    if backend() == "tpu":
        if env not in ("", "pallas"):
            raise ValueError(f"REPRO_KERNEL={env!r} on a TPU backend: the "
                             "device path runs the compiled Pallas kernels")
        return "pallas"
    return env or "xla"


def default_interpret() -> bool:
    """Pallas interpret-mode default: real Mosaic compile only on TPU."""
    env = _env("REPRO_KERNEL_INTERPRET")
    interpret = env not in ("0", "false") if env else None
    if backend() == "tpu":
        if interpret:
            raise ValueError("REPRO_KERNEL_INTERPRET asks for the Pallas "
                             "interpreter on a TPU backend")
        return False
    return True if interpret is None else interpret


def resolve(impl: str | None = None, interpret: bool | None = None
            ) -> tuple[str, bool]:
    """Resolve ``(impl, interpret)``: explicit call-site values win, then
    the backend's default and the environment, as set out above."""
    if impl is None:
        impl = default_impl()
    elif impl not in VALID_IMPLS:
        raise ValueError(f"unknown impl {impl!r}; choose from {VALID_IMPLS}")
    if interpret is None:
        interpret = default_interpret()
    return impl, bool(interpret)
