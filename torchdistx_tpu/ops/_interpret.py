"""Where a pallas kernel's interpret mode is decided.

Every kernel entry point (:func:`.flash_attention.flash_attention`,
:func:`.paged_attention.paged_attention`,
:func:`..parallel.ring_flash.ring_flash_attention`,
:func:`.autotune.tune_flash_blocks`) resolves its ``interpret`` argument
here and nowhere else: compiled by Mosaic on a TPU backend, interpreted
everywhere else.  An interpreted kernel is a correctness vehicle for the
CPU test-suite, never a serving or training path, so every resolution to
interpret mode is counted in ``tdx.ops.interpreted_calls`` — a run that
was meant for the chip (``chip_smoke.py``) fails unless the counter is
still zero at the end.  The count is per kernel *construction* (the
Python body of a jitted caller runs once per trace), which is the event
that decides what the compiled program contains.
"""

from __future__ import annotations

from typing import Optional

import jax

from .. import observe

__all__ = ["interpreted_calls", "resolve_interpret"]


def resolve_interpret(interpret: Optional[bool] = None) -> bool:
    """``interpret`` if the caller forced it, else True off-TPU."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if interpret:
        observe.counter("tdx.ops.interpreted_calls").inc()
    return interpret


def interpreted_calls() -> int:
    """How many kernel constructions resolved to interpret mode so far."""
    return int(observe.counter("tdx.ops.interpreted_calls").value)
