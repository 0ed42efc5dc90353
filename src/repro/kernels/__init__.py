"""Pallas TPU kernels and their jnp references.

Kernels lower to Mosaic on a TPU and run under the Pallas interpreter
on the CPU (:mod:`repro.runtime` decides). The ``*_packed``
variants are the bit-plane packed executors (rows packed 32-per-uint32
word, bitwise gate evaluation); backends select them via ``pack=true``
policy — see :mod:`repro.engine.backends`.
"""
from .crossbar_step import crossbar_run_pallas_packed
from .ops import crossbar_run, crossbar_run_ref
from .ref import crossbar_run_ref_packed

__all__ = ["crossbar_run", "crossbar_run_ref",
           "crossbar_run_ref_packed", "crossbar_run_pallas_packed"]
