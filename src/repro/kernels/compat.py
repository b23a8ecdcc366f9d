"""The Pallas TPU spellings every kernel in this package goes through.

Each kernel builds its ``pallas_call``, compiler params and VMEM scratch
through this module instead of importing ``jax.experimental.pallas.tpu``
itself, so the Pallas surface the package depends on is named in one file:

* :func:`tpu_compiler_params` builds ``pltpu.CompilerParams``;
* :func:`pallas_call` is ``pl.pallas_call`` with the ``interpret=`` keyword
  (the Pallas interpreter, how every kernel runs on the CPU);
* :func:`vmem` allocates ``pltpu.VMEM`` scratch;
* :func:`tpu_available` answers the registry's ``auto`` mode question.

Only the spellings of the installed JAX (``requirements.txt``) are kept;
an unknown hint field is a ``TypeError`` at the call site. See
``docs/kernels.md`` for how this module and the ``(op, backend, mode)``
registry fit together.
"""
from __future__ import annotations

from typing import Any, Callable, Sequence

import jax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def tpu_available() -> bool:
    """True if the default JAX backend is a real TPU."""
    return jax.default_backend() == "tpu"


def tpu_compiler_params(
    *, dimension_semantics: Sequence[str] | None = None, **hints: Any
) -> pltpu.CompilerParams:
    """``pltpu.CompilerParams`` with the grid's dimension semantics."""
    if dimension_semantics is not None:
        hints["dimension_semantics"] = tuple(dimension_semantics)
    return pltpu.CompilerParams(**hints)


def vmem(shape: Sequence[int], dtype: Any) -> Any:
    """A VMEM scratch buffer of ``shape`` and ``dtype``."""
    return pltpu.VMEM(tuple(shape), dtype)


def pallas_call(
    kernel: Callable[..., None],
    *,
    interpret: bool = False,
    **kwargs: Any,
) -> Callable[..., Any]:
    """``pl.pallas_call``; ``interpret=True`` runs the body on the interpreter."""
    return pl.pallas_call(kernel, interpret=interpret, **kwargs)
