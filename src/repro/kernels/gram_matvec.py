"""Pallas TPU kernel for the paper's per-task computation
h(X) = X (X^T theta)  — the linear-regression DGD hot spot (Sec. VI).

TPU adaptation (DESIGN.md §6): never materialize the (d, d) Gram matrix.
Two MXU-tiled passes over X held in (128-aligned) VMEM blocks:

  pass 1:  u[j]  = sum_i X[i, j]^T theta[i]     (grid: d-tiles x b-tiles)
  pass 2:  y[i]  = sum_j X[i, j] u[j]           (grid: b-tiles x d-tiles)

Each pass accumulates its output block across the sequential TPU grid axis
(zero-init on the first visit) — the standard Pallas reduction pattern.
Vectors are carried as (n, 1) 2-D refs (TPU layout requirement).
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BLOCK_D = 256
DEFAULT_BLOCK_B = 256
# The kernel computes in float32, as its oracle does.  Mosaic's default
# contraction precision may round f32 operands to bf16 on the MXU; the
# products here are matrix-vector, bound by reading X, so full precision
# costs little.
_F32 = jax.lax.Precision.HIGHEST


def default_interpret(*, tpu_only: bool = False) -> bool:
    """Interpret Pallas kernels only when no accelerator is attached: on an
    accelerator backend the same BlockSpecs compile natively; on CPU
    interpret mode is the only way to run them.  Kernels using TPU-specific
    primitives (e.g. ``pltpu.VMEM`` scratch) pass ``tpu_only=True`` so they
    stay interpreted on GPU, where Triton cannot lower them."""
    compiled = ("tpu",) if tpu_only else ("tpu", "gpu", "cuda", "rocm")
    return jax.default_backend() not in compiled


def resolve_interpret(interpret: bool | None, *, tpu_only: bool = False
                      ) -> bool:
    return default_interpret(tpu_only=tpu_only) if interpret is None \
        else interpret


def _xt_theta_kernel(x_ref, th_ref, u_ref):
    """u[b_tile] += X[d_tile, b_tile]^T theta[d_tile]; grid (nd, nb)."""
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        u_ref[...] = jnp.zeros_like(u_ref)

    x = x_ref[...].astype(jnp.float32)          # (bd, bb)
    th = th_ref[...].astype(jnp.float32)        # (bd, 1)
    u_ref[...] += jnp.dot(x.T, th, precision=_F32,
                          preferred_element_type=jnp.float32)


def _x_u_kernel(x_ref, u_ref, y_ref):
    """y[d_tile] += X[d_tile, b_tile] u[b_tile]; grid (nb, nd)."""
    j = pl.program_id(0)

    @pl.when(j == 0)
    def _():
        y_ref[...] = jnp.zeros_like(y_ref)

    x = x_ref[...].astype(jnp.float32)          # (bd, bb)
    u = u_ref[...].astype(jnp.float32)          # (bb, 1)
    y_ref[...] += jnp.dot(x, u, precision=_F32,
                          preferred_element_type=jnp.float32)


def gram_matvec_pallas(X: jax.Array, theta: jax.Array, *,
                       block_d: int = DEFAULT_BLOCK_D,
                       block_b: int = DEFAULT_BLOCK_B,
                       interpret: bool | None = None) -> jax.Array:
    """h(X) = X (X^T theta). X (d, b), theta (d,) -> (d,). ``interpret``
    defaults to backend-aware: compiled on TPU/GPU, interpreted on CPU."""
    interpret = resolve_interpret(interpret)
    d, b = X.shape
    bd, bb = min(block_d, d), min(block_b, b)
    pad_d = (-d) % bd
    pad_b = (-b) % bb
    Xp = jnp.pad(X, ((0, pad_d), (0, pad_b))) if (pad_d or pad_b) else X
    thp = jnp.pad(theta, (0, pad_d)) if pad_d else theta
    dp, bp = Xp.shape
    nd, nb = dp // bd, bp // bb
    th2 = thp[:, None]

    u = pl.pallas_call(
        _xt_theta_kernel,
        grid=(nd, nb),
        in_specs=[pl.BlockSpec((bd, bb), lambda i, j: (i, j)),
                  pl.BlockSpec((bd, 1), lambda i, j: (i, 0))],
        out_specs=pl.BlockSpec((bb, 1), lambda i, j: (j, 0)),
        out_shape=jax.ShapeDtypeStruct((bp, 1), jnp.float32),
        interpret=interpret,
        name="gram_xt_theta",
    )(Xp, th2)

    y = pl.pallas_call(
        _x_u_kernel,
        grid=(nb, nd),
        in_specs=[pl.BlockSpec((bd, bb), lambda j, i: (i, j)),
                  pl.BlockSpec((bb, 1), lambda j, i: (j, 0))],
        out_specs=pl.BlockSpec((bd, 1), lambda j, i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((dp, 1), jnp.float32),
        interpret=interpret,
        name="gram_x_u",
    )(Xp, u)

    return y[:d, 0].astype(X.dtype)
