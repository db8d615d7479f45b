"""Pallas execution mode: compiled on TPU, interpreter everywhere else.

Every kernel wrapper resolves its ``interpret`` argument here, so the
rule lives in one place: ``None`` picks the mode from the backend, and
asking for the interpreter while the backend is a TPU is an error — the
interpreter is a CPU test vehicle, never a silent fallback on the chip.
"""

from __future__ import annotations

import jax


def resolve_interpret(interpret: bool | None) -> bool:
    on_tpu = jax.default_backend() == "tpu"
    if interpret is None:
        return not on_tpu
    if interpret and on_tpu:
        raise ValueError(
            "Pallas interpret mode was requested on a TPU backend; the "
            "kernels run compiled there (pass interpret=None or False)")
    return bool(interpret)
