"""Pallas TPU kernels for the paper's hot spots (attention, patch
embedding, SSD chunks).

Each kernel compiles to a Mosaic custom call on a TPU and runs through
the Pallas interpreter on the CPU, which is where the tests run. The
choice follows the platform JAX initialised (:func:`interpret_mode`);
there is no switch for it.
"""
from __future__ import annotations

import jax


def interpret_mode() -> bool:
    """Whether a Pallas kernel runs in the interpreter: only where JAX's
    default backend is the CPU. A TPU never interprets."""
    return jax.default_backend() == "cpu"
