"""The paper's tiled Cholesky (dpotrf) as a ``@taskgraph`` region, its input
and the comparison that decides ``correct``.

The region is user code: potrf/trsm/syrk/gemm tasks over the lower tiles of
an (nb x nb)-tile SPD matrix (arXiv 2212.04771, section 6). It is copied
here, and not imported from the examples, so that an edit there cannot move
the benchmark.
"""
from __future__ import annotations

import itertools

import jax
import jax.numpy as jnp

#: Regions are registered by source location and name, so every region
#: built in one process gets a number of its own (the first gets 0, so the
#: programs of a process's first region, and their cache keys, never vary).
_BUILDS = itertools.count()


def build(nb: int):
    """A taskgraph region factoring an (nb x nb)-tile SPD matrix."""
    from repro.core import taskgraph

    def potrf(a):
        return jnp.linalg.cholesky(a)

    def trsm(l_kk, a):                      # A @ L_kk^-T
        return jax.scipy.linalg.solve_triangular(l_kk, a.T, lower=True).T

    def syrk(a, l):                         # A - L L^T
        return a - l @ l.T

    def gemm(a, l1, l2):                    # A - L1 L2^T
        return a - l1 @ l2.T

    @taskgraph(name=f"bench_cholesky_{nb}_{next(_BUILDS)}")
    def region(g, **tiles):
        for k in range(nb):
            g.task(potrf, ins=[f"A{k}_{k}"], outs=[f"L{k}_{k}"],
                   name=f"potrf{k}")
            for i in range(k + 1, nb):
                g.task(trsm, ins=[f"L{k}_{k}", f"A{i}_{k}"],
                       outs=[f"L{i}_{k}"], name=f"trsm{i}_{k}")
            for i in range(k + 1, nb):
                g.task(syrk, ins=[f"A{i}_{i}", f"L{i}_{k}"],
                       outs=[f"A{i}_{i}"], name=f"syrk{i}_{k}")
                for j in range(k + 1, i):
                    g.task(gemm, ins=[f"A{i}_{j}", f"L{i}_{k}", f"L{j}_{k}"],
                           outs=[f"A{i}_{j}"], name=f"gemm{i}_{j}_{k}")

    return region


def task_count(nb: int) -> int:
    """potrf + trsm + syrk + gemm tasks of an nb-tile factorization."""
    return nb + 2 * (nb * (nb - 1) // 2) + nb * (nb - 1) * (nb - 2) // 6


def make_input(n: int, nb: int, key) -> tuple[jax.Array, dict]:
    """A seeded SPD matrix made on the device in one call, and its lower tiles."""
    bs = n // nb

    @jax.jit
    def make(key):
        m = jax.random.normal(key, (n, n), jnp.float32)
        a = m @ m.T + n * jnp.eye(n, dtype=jnp.float32)
        tiles = {f"A{i}_{j}": a[i * bs:(i + 1) * bs, j * bs:(j + 1) * bs]
                 for i in range(nb) for j in range(i + 1)}
        return a, tiles

    with jax.default_matmul_precision("highest"):
        return make(key)


def assemble(out: dict, n: int, nb: int) -> jax.Array:
    """The full lower-triangular factor from the region's L tiles."""
    bs = n // nb
    zero = jnp.zeros((bs, bs), jnp.float32)
    return jnp.concatenate([
        jnp.concatenate([out[f"L{i}_{j}"] if j <= i else zero
                         for j in range(nb)], axis=1)
        for i in range(nb)], axis=0)


@jax.jit
def _rel_err(x, ref):
    return jnp.linalg.norm(x - ref) / jnp.linalg.norm(ref)


def reference(a: jax.Array) -> jax.Array:
    """The plain factor: XLA's Cholesky of the whole matrix at f32."""
    with jax.default_matmul_precision("highest"):
        return jax.jit(jnp.linalg.cholesky)(a)


def compare(l: jax.Array, l_ref: jax.Array) -> dict[str, float]:
    """Normwise relative error of a whole factor against the reference."""
    with jax.default_matmul_precision("highest"):
        return {"l_rel_err": float(_rel_err(l, l_ref))}


# ------------------------------------------------------------------ control

def _dot3(x, y):
    """``x @ y`` from three bf16 products, as a ``high`` precision pass does.

    Each operand splits into a bf16 head and a bf16 tail, cut by masking the
    low 16 bits: a compiler may not fold a bit mask away, as it may a round
    trip through bf16, so the control computes the same on every backend.
    """
    def head(v):
        bits = jax.lax.bitcast_convert_type(v, jnp.uint32)
        return jax.lax.bitcast_convert_type(bits & jnp.uint32(0xFFFF0000),
                                            jnp.float32)

    xh, yh = head(x), head(y)
    xl, yl = head(x - xh), head(y - yh)
    dot = lambda p, q: jnp.dot(p, q, precision="highest")  # noqa: E731
    return dot(xh, yh) + (dot(xh, yl) + dot(xl, yh))


def control_factor(a: jax.Array, nb: int) -> jax.Array:
    """The reference's blocked factor with its trailing updates in three bf16
    passes: the precision one step below the configuration's f32.

    It stands in for the region and must fail :func:`compare`'s limit.
    """
    n = a.shape[0]
    bs = n // nb

    @jax.jit
    def factor(a):
        l = jnp.zeros_like(a)
        for k in range(nb):
            s, e = k * bs, (k + 1) * bs
            lkk = jnp.linalg.cholesky(a[s:e, s:e])
            l21 = jax.scipy.linalg.solve_triangular(
                lkk, a[e:, s:e].T, lower=True).T
            l = l.at[s:e, s:e].set(lkk).at[e:, s:e].set(l21)
            a = a.at[e:, e:].add(-_dot3(l21, l21.T))
        return l

    with jax.default_matmul_precision("highest"):
        return factor(a)
