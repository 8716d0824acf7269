"""``impl=`` resolution shared by the kernel wrappers.

``"auto"`` picks by platform: the compiled Pallas kernel on TPU, the
pure-jnp reference on CPU, and an error anywhere else — never a silent
fallback.  Interpret mode runs only when asked for by name
(``"pallas_interpret"``).
"""
from __future__ import annotations

from typing import Tuple

import jax

IMPLS = ("auto", "pallas", "pallas_interpret", "jnp")


def use_pallas(impl: str) -> Tuple[bool, bool]:
    """-> (run the Pallas kernel, run it in interpret mode)."""
    if impl == "auto":
        platform = jax.default_backend()
        if platform == "tpu":
            return True, False
        if platform == "cpu":
            return False, False
        raise ValueError(
            f"impl='auto' has no kernel choice for platform {platform!r}; "
            f"pass one of {IMPLS[1:]} explicitly")
    if impl == "pallas":
        return True, False
    if impl == "pallas_interpret":
        return True, True
    if impl == "jnp":
        return False, False
    raise ValueError(f"unknown impl {impl!r} (expected one of {IMPLS})")
