"""Pallas execution mode, derived from the backend in one place."""
from __future__ import annotations

from typing import Optional

import jax


def resolve_interpret(interpret: Optional[bool] = None) -> bool:
    """Whether a ``pallas_call`` runs in the Pallas interpreter.

    ``None`` (every kernel entry's default) derives the mode from the
    backend: interpret on the CPU, where no Mosaic compiler exists, and
    native everywhere else.  An explicit bool wins, so a test may still
    ask for the interpreter on a TPU; no default picks it there.
    """
    if interpret is None:
        return jax.default_backend() == "cpu"
    return bool(interpret)
