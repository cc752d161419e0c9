"""Where the Pallas kernels run: compiled by Mosaic on a TPU, emulated by
the Pallas interpreter on any other backend.

Every kernel entry point takes ``interpret=None`` and resolves it here,
so a call site that omits the argument compiles for the chip when one is
present and never silently runs the interpreter there."""
from __future__ import annotations

from typing import Optional

import jax


def default_interpret() -> bool:
    """True unless JAX's default device is a TPU."""
    return jax.devices()[0].platform != "tpu"


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """An explicit ``interpret`` wins; None means ``default_interpret()``."""
    return default_interpret() if interpret is None else bool(interpret)
