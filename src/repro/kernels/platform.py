"""Platform dispatch for every Pallas kernel of the package.

A kernel runs compiled (Mosaic) on a TPU and through the Pallas
interpreter on the CPU, which is how the test suite exercises it.  The
choice is made by :func:`jax.lax.platform_dependent` at lowering time,
from the platform the surrounding computation is compiled for, so no
caller passes or stores an ``interpret`` flag and a TPU run can never
fall into the interpreter.  Any other platform has no branch and fails
to lower.
"""
from __future__ import annotations

from jax import lax
from jax.experimental import pallas as pl

__all__ = ["pallas_call"]


def pallas_call(kernel, **kwargs):
    """``pl.pallas_call(kernel, **kwargs)``, compiled on a TPU and
    interpreted on the CPU."""
    compiled = pl.pallas_call(kernel, **kwargs)
    interpreted = pl.pallas_call(kernel, interpret=True, **kwargs)

    def call(*args):
        return lax.platform_dependent(*args, cpu=interpreted, tpu=compiled)
    return call
