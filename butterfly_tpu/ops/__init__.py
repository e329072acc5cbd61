"""Pallas attention kernels, and the one rule for where they run.

The kernels are written for Mosaic (the TPU compiler). The rule, kept
here so the engines and all three kernel modules agree on it:

* on the CPU backend a kernel called directly runs in Pallas interpret
  mode (tests cover the exact kernel code there), and the engines leave
  kernels off and use the `jnp` paths;
* on any other backend the engines turn kernels on and the kernels are
  compiled. Interpret mode there is an error, never a fallback: a
  device whose platform string this code does not know must fail in the
  compiler, not quietly serve an interpreted or dense program.

`record_kernels` / `note_kernel` let an engine learn, while its
programs trace, which kernels they hold and whether a call site that
wanted a kernel took the dense path instead; the server reports that on
`/health` and `chip_smoke.py` fails on it.
"""
from __future__ import annotations

import contextlib
import contextvars
from typing import Dict, Optional

import jax

_KERNEL_LOG: contextvars.ContextVar = contextvars.ContextVar(
    "butterfly_kernel_log", default=None)


def kernels_default() -> bool:
    """Should an engine route attention through the Pallas kernels?"""
    return jax.default_backend() != "cpu"


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """The `interpret` flag a pallas_call gets: None picks interpret
    mode on the CPU backend and compilation everywhere else; asking for
    interpret mode on a non-CPU backend raises."""
    cpu = jax.default_backend() == "cpu"
    if interpret is None:
        return cpu
    if interpret and not cpu:
        raise RuntimeError(
            "Pallas interpret mode was requested on the "
            f"{jax.default_backend()!r} backend: interpret mode is for "
            "the CPU backend only, kernels compile everywhere else")
    return bool(interpret)


def sublane_multiple(dtype) -> int:
    """Rows in one Mosaic tile of `dtype`: 8 for 4-byte types, 16 for
    bf16, 32 for int8. A block whose second-minor dim is sized from a
    short sequence is rounded up to this (the wrappers pad to it)."""
    import jax.numpy as jnp
    return 8 * max(1, 4 // jnp.dtype(dtype).itemsize)


def kernel_mode(use_kernels: bool) -> str:
    """'off', 'interpret' or 'compiled': how an engine built with
    `use_kernels` runs its attention kernels on this backend."""
    if not use_kernels:
        return "off"
    return "interpret" if resolve_interpret(None) else "compiled"


@contextlib.contextmanager
def record_kernels(log: Dict[str, int]):
    """Count into `log` every kernel call site traced inside the block
    (keys like 'paged_int8_win:compiled', plus 'dense_fallback')."""
    token = _KERNEL_LOG.set(log)
    try:
        yield log
    finally:
        _KERNEL_LOG.reset(token)


def note_kernel(name: str, interpret: Optional[bool] = None) -> None:
    """Called at trace time by the kernel wrappers (and by the layer
    body when a wanted kernel gave way to the dense path)."""
    log = _KERNEL_LOG.get()
    if log is None:
        return
    if interpret is not None:
        name += ":interpret" if interpret else ":compiled"
    log[name] = log.get(name, 0) + 1
