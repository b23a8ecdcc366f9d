"""Pallas TPU kernels (with jnp oracles) for the performance-critical ops.

``compat`` names the Pallas TPU spellings every kernel uses (one place to
change on a jax upgrade); ``registry`` maps ``(op, backend, mode)`` to a substrate and
owns the global kernel-mode switch; ``ops`` exposes the registry-dispatched
public entry points used by models, executors, and benchmarks.
"""
from . import compat, ops, ref, registry
from .flash_attention import flash_attention
from .moe_gmm import grouped_matmul
from .rmsnorm import rmsnorm
from .ssd_scan import ssd

__all__ = ["compat", "ops", "ref", "registry",
           "flash_attention", "grouped_matmul", "rmsnorm", "ssd"]
