"""Dispatch of the Mamba-2 decode recurrence over the serving state arena.

``ssm_decode`` runs one layer's recurrence for every lane of a step under
the named scope ``ssm.kernel``. Two backends with one contract:

* ``impl="ref"`` — the pure-jax oracle (``ref.py``), which the engine
  runs off the TPU;
* ``impl="pallas"`` — the Pallas kernel (``kernel.py``, the call named
  ``ssm_decode``), which reads and writes the arena in place; the engine
  runs it whenever JAX runs on a TPU (``repro.kernels.backend``).
"""

from __future__ import annotations

import jax

from repro.kernels.ssm_decode import ref
from repro.kernels.ssm_decode.kernel import ssm_decode_kernel


def ssm_decode(state, layer, x, dt, A, B, C, D, *, mask=None,
               impl: str = "ref", interpret: bool | None = None):
    """``h ← h·exp(dt·A) + (dt·x) ⊗ B``, ``y = Σ_n h·C + D·x`` on layer
    ``layer`` of the arena ``state`` (Lm, b, H, P, N) f32, for the lanes
    where ``mask`` (b,) holds (the rest keep their state bitwise). x
    (b, H, P); dt (b, H) f32; A, D (H,) f32; B, C (b, N). Returns
    ``(y (b, H, P) f32, state')``; pass a donated arena."""
    with jax.named_scope("ssm.kernel"):
        if impl == "pallas":
            return ssm_decode_kernel(state, layer, x, dt, A, B, C, D, mask,
                                     interpret=interpret)
        if impl == "ref":
            return ref.ssm_decode(state, layer, x, dt, A, B, C, D, mask)
    raise ValueError(f"unknown ssm_decode impl {impl!r}")
