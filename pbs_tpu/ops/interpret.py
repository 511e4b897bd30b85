"""When a Pallas kernel runs in interpret mode."""

from __future__ import annotations

import jax


def resolve_interpret(interpret: bool | None) -> bool:
    """``None`` means: interpret only where the default backend is
    positively the CPU (the tests), compile through Mosaic everywhere
    else. The test is not ``!= "tpu"``: on a chip whose platform goes
    by another name that rule ran the interpreter on the device without
    saying so. Interpret mode on an accelerator has to be asked for."""
    if interpret is None:
        return jax.default_backend() == "cpu"
    return interpret
