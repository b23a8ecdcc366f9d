"""Registry-driven dispatch wrappers for the performance-critical ops.

Every public function here resolves its implementation through
:mod:`repro.kernels.registry` — one table maps ``(op, backend, mode)`` to a
substrate instead of per-function if/elif chains. The substrates:

  * ``pallas``    — compiled Pallas kernels (TPU), built through
                    :mod:`repro.kernels.compat`;
  * ``ref``       — memory-sane pure-XLA/jnp references (exact numerics,
                    the default on CPU);
  * ``interpret`` — the Pallas kernel bodies on the interpreter (CPU
                    debugging / parity testing of the real kernel code).

``set_kernel_mode(...)`` / env ``REPRO_KERNELS={auto,pallas,ref,interpret}``
pick the substrate globally (``auto`` = pallas on TPU, ref elsewhere); the
env var is validated eagerly at import. Replay executors pin the resolved
mode once at lowering time via ``registry.kernel_mode_scope``.
"""
from __future__ import annotations

import functools
from typing import Literal

from . import flash_attention as _fa
from . import moe_gmm as _gmm
from . import ref as _ref
from . import registry
from . import rmsnorm as _rms
from . import ssd_scan as _ssd
from . import xla_attention as _xla

Mode = Literal["auto", "pallas", "ref", "interpret"]

# Mode state lives in the registry; re-exported here for callers that
# predate it (tests, benchmarks, notebooks).
set_kernel_mode = registry.set_kernel_mode
kernel_mode = registry.kernel_mode


# ------------------------------------------------------------ substrates

def _attention_ref(q, k, v, *, causal=True, window=None, chunk=None,
                   scale=None, q_offset=0, q_chunk=2048):
    """Pure-XLA attention (exact numerics, bounded live scores)."""
    if not causal:
        return _xla.sdpa_cross(q, k, v, scale=scale)
    if window:
        return _xla.sdpa_sliding(q, k, v, window=window, scale=scale)
    if chunk:
        return _xla.sdpa_chunked(q, k, v, chunk=chunk, scale=scale)
    return _xla.sdpa_full(q, k, v, causal=causal, scale=scale,
                          q_offset=q_offset, chunk=q_chunk)


def _attention_pallas(q, k, v, *, causal=True, window=None, chunk=None,
                      scale=None, q_offset=0, q_chunk=2048, interpret=False):
    """Flash-attention Pallas kernel (q_chunk is a ref-path knob; unused)."""
    del q_chunk
    return _fa.flash_attention(q, k, v, causal=causal, window=window,
                               chunk=chunk, scale=scale, q_offset=q_offset,
                               interpret=interpret)


def _ssd_ref(x, dt, A, Bm, Cm, D=None, init_state=None, *, chunk=128):
    """Blockwise jnp SSD (chunk clamped to the sequence length)."""
    return _ref.ssd_chunked_ref(x, dt, A, Bm, Cm, D=D, init_state=init_state,
                                chunk=min(chunk, x.shape[1]))


def _ssd_pallas(x, dt, A, Bm, Cm, D=None, init_state=None, *, chunk=128,
                interpret=False):
    return _ssd.ssd(x, dt, A, Bm, Cm, D=D, init_state=init_state,
                    chunk=chunk, interpret=interpret)


def _gmm_pallas(x, w, *, interpret=False):
    return _gmm.grouped_matmul(x, w, interpret=interpret)


def _rmsnorm_pallas(x, w, eps=1e-6, residual=None, *, interpret=False):
    return _rms.rmsnorm(x, w, eps=eps, residual=residual, interpret=interpret)


def _register_defaults() -> None:
    """Populate the registry with this package's substrates.

    All entries are platform-wildcards: the jnp references and the
    interpreter run anywhere, and an explicit mode="pallas" off-TPU runs
    the compiled-path code too (it fails loudly in Mosaic if lowering
    breaks — useful under REPRO_KERNELS=pallas on CPU CI). A future
    GPU/Triton PR adds ``backend="gpu"`` rows here (or in its own package)
    without touching the dispatch functions below; backend-specific rows
    take precedence over these wildcards.
    """
    table = {
        "attention": (_attention_ref, _attention_pallas),
        "ssd": (_ssd_ref, _ssd_pallas),
        "grouped_matmul": (_ref.grouped_matmul_ref, _gmm_pallas),
        "rmsnorm": (_ref.rmsnorm_ref, _rmsnorm_pallas),
    }
    for op, (ref_fn, pallas_fn) in table.items():
        registry.register(op, "ref", fn=ref_fn)
        registry.register(op, "pallas", fn=pallas_fn)
        registry.register(op, "interpret",
                          fn=functools.partial(pallas_fn, interpret=True),
                          doc=f"{op} Pallas body on the interpreter")


_register_defaults()


# -------------------------------------------------------------- public ops

def attention(q, k, v, *, causal=True, window=None, chunk=None, scale=None,
              q_offset=0, q_chunk=2048):
    return registry.dispatch("attention", q, k, v, causal=causal,
                             window=window, chunk=chunk, scale=scale,
                             q_offset=q_offset, q_chunk=q_chunk)


def ssd(x, dt, A, Bm, Cm, D=None, init_state=None, *, chunk=128):
    return registry.dispatch("ssd", x, dt, A, Bm, Cm, D=D,
                             init_state=init_state, chunk=chunk)


def grouped_matmul(x, w):
    return registry.dispatch("grouped_matmul", x, w)


def rmsnorm(x, w, eps=1e-6, residual=None):
    return registry.dispatch("rmsnorm", x, w, eps=eps, residual=residual)
