"""Pallas TPU kernels for the MoE compute hot spots + decode attention.

Each kernel has a pure-jnp oracle in ref.py; ops.py holds the jit'd
wrappers and the routing-table builders. interpret mode is auto-detected
per platform (platform.default_interpret, DESIGN.md §6): interpreter on
CPU for correctness, compiled with MXU-aligned BlockSpecs on TPU.
"""
from repro.kernels import ops, ref
from repro.kernels.flash_decode import flash_decode, flash_decode_paged
from repro.kernels.grouped_ffn import grouped_matmul
from repro.kernels.moe_dispatch import combine, dispatch
from repro.kernels.moe_megakernel import fused_moe_ffn
from repro.kernels.platform import (default_interpret, force_interpret,
                                    resolve_interpret)

__all__ = ["combine", "default_interpret", "dispatch", "flash_decode",
           "flash_decode_paged", "force_interpret", "fused_moe_ffn",
           "grouped_matmul", "ops", "ref", "resolve_interpret"]
