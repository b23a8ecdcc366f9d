"""repro: Taskgraph — a low-contention tasking framework for JAX/TPU.

Reproduction + production framework for Yu, Royuela & Quiñones,
"Taskgraph: A Low Contention OpenMP Tasking Framework" (2022), adapted to
the TPU/JAX execution model. See docs/architecture.md.
"""

__version__ = "0.1.0"
