"""int8 fixed-point matmul Pallas kernel (the DSP48E1 Q-format arithmetic,
MXU edition): int8 × int8 → int32 accumulation, per-cout dequant epilogue.

The paper's accelerator multiplies Q3.4 activations by Q2.5 coefficients in
the DSP slices; on TPU the same integer arithmetic maps onto the MXU's
int8 path. Accumulation is exact (int32), so the kernel is bit-identical
to ``ref.int8_matmul_ref`` — tests assert equality, not closeness.

``scale`` is the dequant row the flush epilogue multiplies the int32
accumulator by: a per-cout ``(N,)`` vector (what the block-sparse conv
epilogue reuses — each output channel carries its own weight scale), or
the legacy scalar ``(1,)`` which is broadcast to every column (the thin
wrapper ``ops.fixed_point_matmul`` still uses).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _kernel(x_ref, w_ref, scale_ref, o_ref, acc_ref):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        x_ref[...], w_ref[...],
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)

    @pl.when(k == pl.num_programs(2) - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(jnp.float32) * scale_ref[...]


@functools.partial(jax.jit, static_argnames=("bm", "bk", "bn", "interpret"))
def int8_matmul(
    x_codes: jnp.ndarray,      # (M, K) int8
    w_codes: jnp.ndarray,      # (K, N) int8
    scale: jnp.ndarray,        # (N,) f32 per-cout dequant row, or (1,) scalar
    *,
    bm: int = 128,
    bk: int = 128,
    bn: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    M, K = x_codes.shape
    _, N = w_codes.shape
    assert M % bm == 0 and K % bk == 0 and N % bn == 0
    if scale.shape == (1,):
        scale = jnp.broadcast_to(scale, (N,))     # scalar: one scale, every cout
    assert scale.shape == (N,), f"scale must be (1,) or ({N},), got {scale.shape}"
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=0,
        grid=(M // bm, N // bn, K // bk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
    )
    return pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.float32),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(x_codes, w_codes, scale.reshape(1, N).astype(jnp.float32))
