"""Fused Kalman fleet update (paper eqs. 6-9) as a Pallas TPU kernel.

This is the control plane's hot loop at fleet scale: a platform tracking
millions of (workload, data-type) estimators updates them all every
monitoring instant.  The update is purely elementwise (memory-bound,
arithmetic intensity ≈ 7 flops / 16 bytes), so the kernel's job is a single
fused HBM→VMEM→HBM pass over (8,128)-aligned VPU tiles — one read and one
write per operand instead of the ~6 intermediate arrays a naive jnp chain
materializes.
"""

from __future__ import annotations

import functools
import warnings

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLOCK_R, BLOCK_C = 256, 128

_WARNED_INTERPRET = False  # the fallback notice fires once per process


def resolve_interpret(interpret: bool | None) -> bool:
    """Platform-aware default: compile the kernel for real on TPU, run the
    Pallas interpreter (plain XLA ops — jittable, scannable) elsewhere.

    The implicit fallback is announced once per process (a UserWarning
    naming the resolved platform): interpreter emulation is bit-compatible
    but carries none of the kernel's fusion benefit, so a benchmark that
    silently landed on it would report meaningless kernel numbers.
    """
    global _WARNED_INTERPRET
    if interpret is None:
        platform = jax.default_backend()
        fallback = platform != "tpu"
        if fallback and not _WARNED_INTERPRET:
            _WARNED_INTERPRET = True
            warnings.warn(
                f"kalman_update: no TPU — resolved platform is "
                f"{platform!r}, running the Pallas kernel in interpret "
                "mode (plain XLA ops; numerically identical, not a "
                "kernel-performance measurement). Pass interpret=False "
                "to require the compiled kernel.", UserWarning,
                stacklevel=3)
        return fallback
    return bool(interpret)


def _kalman_kernel(b_ref, pi_ref, meas_ref, mask_ref, b_out, pi_out,
                   *, sigma_z2: float, sigma_v2: float):
    b = b_ref[...]
    pi = pi_ref[...]
    meas = meas_ref[...]
    mask = mask_ref[...] != 0

    pi_minus = pi + sigma_z2                       # eq. 6
    kappa = pi_minus / (pi_minus + sigma_v2)       # eq. 7
    b_new = b + kappa * (meas - b)                 # eq. 8
    pi_new = (1.0 - kappa) * pi_minus              # eq. 9

    b_out[...] = jnp.where(mask, b_new, b)
    pi_out[...] = jnp.where(mask, pi_new, pi)


def kalman_fused(b_hat, pi, b_meas_prev, mask,
                 sigma_z2: float, sigma_v2: float,
                 interpret: bool | None = None):
    """All inputs (W, K) f32; mask int8/bool.  Returns (b_hat', pi').

    ``interpret=None`` resolves platform-aware: compiled on TPU, emulated
    elsewhere (the interpreter lowers to plain XLA ops, so it jits and
    scans fine on CPU).
    """
    interpret = resolve_interpret(interpret)
    w, k = b_hat.shape
    br, bc = min(BLOCK_R, w), min(BLOCK_C, k)
    if w % br != 0 or k % bc != 0:
        # ValueError, not assert: under ``python -O`` a stripped assert
        # would let a partial grid silently skip the trailing rows.
        raise ValueError(
            f"kalman_fused needs (W, K)=({w}, {k}) divisible by the "
            f"({br}, {bc}) block — pad the filter bank to a multiple")
    kernel = functools.partial(_kalman_kernel, sigma_z2=sigma_z2,
                               sigma_v2=sigma_v2)
    grid = (w // br, k // bc)
    spec = pl.BlockSpec((br, bc), lambda i, j: (i, j))
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[spec, spec, spec, spec],
        out_specs=[spec, spec],
        out_shape=[jax.ShapeDtypeStruct((w, k), b_hat.dtype)] * 2,
        interpret=interpret,
        name="kalman_update",
    )(b_hat, pi, b_meas_prev, mask.astype(jnp.int8))
