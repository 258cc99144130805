"""One ``pallas_call`` for every kernel in this package.

Two rules hold for every kernel here:

* **Interpret mode is for the CPU only.**  ``interpret=None`` (every
  kernel's default) picks the interpreter when JAX's default backend is
  the CPU and the compiled kernel otherwise.  Asking for the interpreter
  on an accelerator raises: an interpreted kernel on a chip is a hidden
  slow path, not a fallback.
* **No 64-bit types reach a kernel body.**  The package turns on x64 for
  exact join counts (``repro/__init__.py``); Mosaic, the TPU kernel
  compiler, has no 64-bit types.  Kernels take and return 32-bit arrays,
  and their bodies are traced with x64 off.
"""
from __future__ import annotations

import jax
from jax.experimental import pallas as pl


def interpret_mode(interpret: bool | None = None) -> bool:
    """Resolve a kernel's ``interpret`` flag against the default backend.

    ``None`` -> interpret exactly when the backend is the CPU.  An explicit
    ``True`` off the CPU raises ``ValueError``; an explicit ``False`` is
    always honoured (compiling for a described TPU from a CPU process)."""
    on_cpu = jax.default_backend() == "cpu"
    if interpret is None:
        return on_cpu
    if interpret and not on_cpu:
        raise ValueError(
            "Pallas interpret mode was requested on the "
            f"{jax.default_backend()!r} backend; kernels run compiled on "
            "an accelerator")
    return bool(interpret)


def pallas_call(kernel, *, interpret: bool | None = None, **kw):
    """``pl.pallas_call`` with :func:`interpret_mode` applied and the
    kernel body traced with x64 off."""
    call = pl.pallas_call(kernel, interpret=interpret_mode(interpret), **kw)

    def run(*args):
        with jax.enable_x64(False):
            return call(*args)

    return run
