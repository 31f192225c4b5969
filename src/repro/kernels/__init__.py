"""Pallas TPU kernel for the PRISM spMTTKRP hot spot.

`mttkrp_kernel` holds the pallas_call body, `ops` the device layout and
jit'd public wrapper, `ref` the pure-jnp oracle.
"""
from .ops import kernel_tensor, mttkrp_pallas
