"""Where the Pallas kernels run: compiled on a TPU, interpreted elsewhere.

Every kernel takes ``interpret=None`` by default and resolves it here, so
the choice follows the backend JAX actually runs on and is made in one
place. On the CPU (the test suite, ``JAX_PLATFORMS=cpu``) kernels run in
Pallas interpret mode; on a TPU they compile to a Mosaic custom call.
Pass ``interpret=False`` explicitly to lower for a TPU that is described
but not attached (ahead-of-time compiles).
"""

from __future__ import annotations

import jax


def on_tpu() -> bool:
    """True when JAX's default backend is a TPU."""
    return jax.default_backend() == "tpu"


def interpret(flag: bool | None = None) -> bool:
    """Resolve a kernel's ``interpret`` argument: an explicit bool wins;
    ``None`` interprets exactly when not running on a TPU."""
    return (not on_tpu()) if flag is None else flag
