# OPTIONAL layer. Add <name>.py (or .cu) + ops.py + ref.py ONLY
# for compute hot-spots the paper itself optimizes with a custom
# kernel. Leave this package empty if the paper has none.
"""Shared kernel-dispatch policy: ONE device decision.

:func:`platform` is the only place the kernels ask which hardware they
run on. Every ``ops.py`` wrapper takes ``interpret: bool | None = None``
and resolves ``None`` through :func:`default_interpret` at trace time —
the Pallas interpreter only on the CPU, the compiled kernel everywhere
else — and ``rerank_score``'s auto impl reads the same function. Tests
that want the interpreter pass ``interpret=True`` explicitly.
"""
from __future__ import annotations

import jax


def platform() -> str:
    """Platform of the default device (``"cpu"``, ``"tpu"``, ...). Any
    backend-initialisation error propagates: a broken TPU runtime must
    fail loudly, not quietly turn into a CPU decision."""
    return jax.devices()[0].platform


def pad_axis(x, mult: int, axis: int):
    """Zero-pad one axis up to the next multiple of ``mult`` (shared by the
    kernel wrappers — padded rows are masked or sliced off by each op)."""
    import jax.numpy as jnp
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    cfg = [(0, 0)] * x.ndim
    cfg[axis] = (0, pad)
    return jnp.pad(x, cfg)


def default_interpret() -> bool:
    """True ⇒ run Pallas kernels in interpreter mode (CPU only).

    Resolution happens when an op is traced; the decision is baked into that
    trace (it is a static argument)."""
    return platform() == "cpu"


def resolve_interpret(interpret) -> bool:
    return default_interpret() if interpret is None else bool(interpret)
