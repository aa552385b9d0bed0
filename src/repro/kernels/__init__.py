"""Pallas TPU kernels for the framework's compute hot-spots.

The paper's contribution is verification tooling (no kernel-level claims);
these kernels are the framework's optional fast paths, written for TPU
(pl.pallas_call + BlockSpec VMEM tiling).  They compile for the chip by
default (``interpret=False``); tests on the CPU pass ``interpret=True``
explicitly and compare against the pure-jnp oracles in ref.py.
"""
from . import ref
