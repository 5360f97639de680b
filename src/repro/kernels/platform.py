"""Where the Pallas kernels run: compiled on a TPU, interpreted on the CPU.

Every kernel entry point takes ``interpret=None`` and resolves it here, so
no caller has to remember to switch the interpreter off on the chip.
"""
from __future__ import annotations

import jax

_INTERPRET = {"cpu": True, "tpu": False}


def interpret_mode(interpret: bool | None = None,
                   platform: str | None = None) -> bool:
    """Resolve a kernel's ``interpret`` flag.

    An explicit bool wins (a compile-only test for a described TPU steers
    it). Otherwise the platform decides - `platform` when given (e.g. the
    platform of a mesh's devices), else `jax.default_backend()`: the Pallas
    interpreter on "cpu", the Mosaic-compiled kernel on "tpu". Any other
    platform has no kernel here and raises.
    """
    if interpret is not None:
        return bool(interpret)
    platform = platform or jax.default_backend()
    if platform not in _INTERPRET:
        raise RuntimeError(
            f"no Pallas kernel route for platform {platform!r}; "
            f"supported: {sorted(_INTERPRET)}")
    return _INTERPRET[platform]
