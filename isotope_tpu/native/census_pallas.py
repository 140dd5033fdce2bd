"""Pallas kernel for the per-step census / WaitGroup-max reduction.

The hop kernel's inner join — for every (request, hop) pair, take each
step's ``max(sleep floor, concurrent-call census)``, mask the unused
step lanes, row-sum into the hop's busy time and keep the exclusive
per-step prefix for child start offsets — is today a chain of four XLA
HLOs (``max``, ``mul``, ``reduce``, ``cumsum``) that each round-trip the
(N, B, P) step grid through HBM.  This module fuses the chain into ONE
hand-written kernel: the grid is tiled over the request and hop axes,
each block streams through VMEM once, and the step axis (small, the
padded script width) is reduced in-register.

Packing (SimParams.packed_carries): the step MASK operand rides as
bfloat16 — its values are exactly 0/1, which bf16 represents exactly,
so the f32 multiply is bit-equal to the f32-mask reference while the
constant's footprint halves.  The step BASE and the census values stay
f32 (latency accumulators are pinned to f32 by the <= 1 ULP contract).

Execution modes:

- ``interpret=True`` (the default everywhere except a TPU backend)
  evaluates the kernel body op-by-op on the host — what the
  equivalence tests (tests/test_census_pallas.py) run, bit-identical
  to the kernel's semantics and within 1 ULP of the XLA reference
  chain;
- on a TPU backend the call compiles through Mosaic.  The compiler
  for the v5e (JAX 0.9.0 / libtpu 0.0.34) REFUSES the kernel as
  written: ``cumsum`` has no Pallas TPU lowering; with it unrolled the
  ``err`` variant dies on the ``bool[:, :, None]`` shape cast; and the
  step axis P (4-8) is the minor dim, padded to 128 lanes, so the
  ``f32[N, B, P]`` operands cost 32x their size in VMEM and HBM
  (tests/test_chip_compile.py pins the verdict).  It needs P off the
  lane axis — a layout change through the engine (ROADMAP S9/D2).

The engine gates every call on ``SimParams.pallas_census``, which is
OFF unless explicitly ``True`` on every backend (since PR 22); there
is no catch and no interpret-mode rescue on a TPU — the compiler's
own error propagates.  With the flag off this module is never
imported and the op-by-op path is byte-identical to PR 5's.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

#: request-axis rows per kernel block; the hop axis is tiled so one
#: block's f32 footprint stays a few MB of VMEM
_ROW_BLOCK = 8
_HOP_BLOCK = 512

#: step grids past this many (B * P) elements skip the kernel — a
#: single row would not fit VMEM comfortably
MAX_GRID_ELEMS = 1 << 21


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


def pack_mask(step_mask: jax.Array) -> jax.Array:
    """The bf16-packed census mask (exact: values are 0/1)."""
    return step_mask.astype(jnp.bfloat16)


def _census_kernel(base_ref, mask_ref, agg_ref, busy_ref, excl_ref,
                   *, has_fail: bool, has_err: bool, fail_ref=None,
                   err_ref=None):
    """One (rows x hops x steps) block of the census join.

    Argument order at call sites is (base, mask, agg[, fail][, err]);
    pallas passes them positionally, so the optional refs arrive via
    the keyword defaults bound by functools.partial below.
    """
    base = base_ref[...]                     # (Hb, P) f32
    mask = mask_ref[...].astype(jnp.float32)  # (Hb, P) bf16 -> f32
    agg = agg_ref[...]                       # (Rb, Hb, P) f32
    dur = jnp.maximum(base[None], agg) * mask[None]
    if has_fail:
        fail = fail_ref[...]                 # (Rb, Hb) i32
        step_ids = jax.lax.broadcasted_iota(
            jnp.int32, dur.shape, dimension=2
        )
        dur = dur * (step_ids <= fail[:, :, None])
    if has_err:
        err = err_ref[...]                   # (Rb, Hb) bool
        dur = dur * ~err[:, :, None]
    run = jnp.cumsum(dur, axis=-1)
    busy_ref[...] = run[:, :, -1]
    excl_ref[...] = run - dur


@functools.lru_cache(maxsize=64)
def _build(n: int, b: int, p: int, has_fail: bool, has_err: bool,
           interpret: bool):
    """Compile one census pallas_call for a padded (n, b, p) grid."""
    from jax.experimental import pallas as pl

    rb = min(_ROW_BLOCK, n)
    hb = min(_HOP_BLOCK, b)
    grid = (n // rb, b // hb)
    in_specs = [
        pl.BlockSpec((hb, p), lambda i, j: (j, 0)),          # base
        pl.BlockSpec((hb, p), lambda i, j: (j, 0)),          # mask
        pl.BlockSpec((rb, hb, p), lambda i, j: (i, j, 0)),   # agg
    ]
    if has_fail:
        in_specs.append(pl.BlockSpec((rb, hb), lambda i, j: (i, j)))
    if has_err:
        in_specs.append(pl.BlockSpec((rb, hb), lambda i, j: (i, j)))
    kernel = functools.partial(
        _census_kernel_dispatch, has_fail=has_fail, has_err=has_err,
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((rb, hb), lambda i, j: (i, j)),
            pl.BlockSpec((rb, hb, p), lambda i, j: (i, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, b), jnp.float32),
            jax.ShapeDtypeStruct((n, b, p), jnp.float32),
        ],
        interpret=interpret,
    )


def _census_kernel_dispatch(*refs, has_fail: bool, has_err: bool):
    """Route pallas' positional refs into the keyword kernel."""
    base_ref, mask_ref, agg_ref = refs[0], refs[1], refs[2]
    k = 3
    fail_ref = err_ref = None
    if has_fail:
        fail_ref = refs[k]
        k += 1
    if has_err:
        err_ref = refs[k]
        k += 1
    busy_ref, excl_ref = refs[k], refs[k + 1]
    _census_kernel(
        base_ref, mask_ref, agg_ref, busy_ref, excl_ref,
        has_fail=has_fail, has_err=has_err,
        fail_ref=fail_ref, err_ref=err_ref,
    )


def supported(num_hops: int, pmax: int) -> bool:
    """Whether the kernel should serve a (B, P) step grid.

    An UPPER bound the chip's compiler has not confirmed: it counts
    dense elements, while Mosaic pads P to 128 lanes and knows the
    request count N — shapes this admits are refused for VMEM or HBM
    on the v5e (module docstring)."""
    return num_hops * pmax <= MAX_GRID_ELEMS


def census(
    step_base: jax.Array,          # (B, P) f32
    step_mask: jax.Array,          # (B, P) f32 or bf16 (packed)
    agg: jax.Array,                # (N, B, P) f32 census (scatter-max out)
    fail_step: Optional[jax.Array] = None,  # (N, B) i32, sentinel >= P
    err: Optional[jax.Array] = None,        # (N, B) bool
    interpret: Optional[bool] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Fused census join: ``(busy, exclusive step prefix)``.

    Semantics (identical to the XLA reference chain):

    .. code-block:: python

        dur = max(step_base, agg) * step_mask
        dur *= (arange(P) <= fail_step[..., None])   # when given
        dur *= ~err[..., None]                       # when given
        busy = dur.sum(-1); excl = cumsum(dur, -1) - dur
    """
    n, b, p = agg.shape
    if interpret is None:
        interpret = _interpret_default()
    mask = step_mask if step_mask.dtype == jnp.bfloat16 else pack_mask(
        step_mask
    )
    rb = min(_ROW_BLOCK, n)
    hb = min(_HOP_BLOCK, b)
    pad_n = (-n) % rb
    pad_b = (-b) % hb
    args = [
        jnp.pad(step_base.astype(jnp.float32), ((0, pad_b), (0, 0))),
        jnp.pad(mask, ((0, pad_b), (0, 0))),
        jnp.pad(agg, ((0, pad_n), (0, pad_b), (0, 0))),
    ]
    if fail_step is not None:
        args.append(jnp.pad(
            fail_step.astype(jnp.int32), ((0, pad_n), (0, pad_b)),
        ))
    if err is not None:
        args.append(jnp.pad(err, ((0, pad_n), (0, pad_b))))
    fn = _build(
        n + pad_n, b + pad_b, p,
        fail_step is not None, err is not None, bool(interpret),
    )
    busy, excl = fn(*args)
    return busy[:n, :b], excl[:n, :b]
