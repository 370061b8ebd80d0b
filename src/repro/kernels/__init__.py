"""Pallas TPU kernels for the GNN hot path (``paramspmm``, ``sddmm``) and
the one place that decides how they run on this platform."""
from __future__ import annotations

import jax


def resolve_backend(backend: str | None = None,
                    interpret: bool | None = None) -> tuple[str, bool]:
    """``(backend, interpret)`` with every ``None`` filled in from the
    platform: on a TPU the compiled Pallas kernels (``"pallas"``,
    ``interpret=False``); elsewhere the pure-JAX engine, and kernels —
    where a caller asks for ``"pallas"`` — in the interpreter
    (``"engine"``, ``interpret=True``).  Explicit arguments always win;
    nothing falls back when a kernel fails to compile."""
    tpu = jax.default_backend() == "tpu"
    if backend is None:
        backend = "pallas" if tpu else "engine"
    if backend not in ("engine", "pallas"):
        raise ValueError(f"backend must be 'engine' or 'pallas', "
                         f"got {backend!r}")
    if interpret is None:
        interpret = not tpu
    return backend, interpret
