"""Fused affine+ReLU+3x3-conv Pallas TPU kernel for ResNet stage-1 shapes.

Why this kernel exists (profiled, docs/DESIGN.md "Where the other half of
peak goes"): the bench's step time is wall-to-wall convolutions, and the
early 64-channel stage is the inefficient part — XLA runs the stage-1
3x3 convs at 18-45% of bf16 peak, streaming [B,32,32,64] activations
from HBM, with the BatchNorm-normalize/ReLU chains between convs compiled
as *separate* loop fusions that cost an extra HBM round trip per tensor
(6.9% of device time on their own). The reference hits the same structure
via cuDNN (`/root/reference/cifar_example_ddp.py:104` lowers to
implicit-gemm kernels); this is the TPU answer, not a translation of it.

The kernel fuses, per batch tile, entirely in VMEM:

    z = relu(x * scale + shift [+ residual])     # the BN-apply epilogue
    y = conv3x3_SAME(z, W)                       # stride 1, C_in=C_out=C

so the normalized activation `z` never exists in HBM — and the conv is a
single MXU contraction per tile ("one-matmul conv"): rows = (b, h, w')
over the padded width, K = (dh, c_in) from three H-shifted input slices,
N = (dw, c_out) packing all three column taps as output blocks, which a
row shift then realigns. For C=64 that is a [rows,192]x[192,192] matmul —
far better MXU occupancy than the K=64, N=64 dots XLA's conv emitter can
use at this channel width.

`scale`/`shift` are per-channel f32 vectors; callers fold whatever affine
they need into them (for BatchNorm: scale = gamma/sqrt(var+eps),
shift = beta - mean*scale). `residual` is the pre-activation skip branch
(added before the ReLU), so one invocation consumes the tail of the
previous block (BN-apply + residual-add + ReLU) and produces the next
conv — a whole stage chains through VMEM. `activate=False` skips the
ReLU for use as a plain (affine-)conv.

Distribution: the op carries a `jax.experimental.custom_partitioning`
rule that shards the batch dimension over the mesh and runs the kernel
on each device's local shard — without it, GSPMD treats the pallas_call
as an opaque replicated op and serializes the hot path (verified on the
8-virtual-device CPU mesh; `tests/test_conv_block.py` pins the sharded
behavior).

Differentiation: `fused_affine_relu_conv` carries a `jax.custom_vjp`
with a hand-written backward that recomputes `z` (cheap elementwise,
verified against autodiff of the unfused statement in tests): the
weight-grad contraction is XLA's; the input-grad conv is XLA's
conv-transpose by default, or — with ``pallas_bwd`` — this same kernel
with spatially-flipped, io-swapped weights (the input-grad of a stride-1
SAME 3x3 conv is another stride-1 SAME 3x3 conv); the affine/ReLU
backward is explicit elementwise math. The kernel compiles for the TPU;
inside `tpu_dp.ops.interpret_kernels()` it runs in the Pallas interpreter,
which is how CPU tests exercise identical code.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.experimental.custom_partitioning import custom_partitioning
from jax.sharding import NamedSharding, PartitionSpec as P

from tpu_dp.ops._partition import (
    batch_axis as _batch_axis,
    interpret as _interpret,
    pad_batch as _pad_batch,
    shape_struct as _shape_struct,
    shard_map_interp as _shard_map_interp,
)

_BLOCK_B = 0  # default: auto (pick images/grid-step from the VMEM budget)
_VMEM_BUDGET_BYTES = 12 * 2**20  # leave headroom under the ~16MB VMEM


def _pad128(n: int) -> int:
    return -(-n // 128) * 128


def _auto_block_b(h: int, w: int, c: int, with_res: bool = False,
                  emit_z: bool = False) -> int:
    """Images per grid step that keep the kernel's working set under the
    VMEM budget: per image the kernel holds x, zp, the dh-concat win, the
    f32 matmul output t (lanes padded to 128), the f32 acc slice, the y
    output plus slack, and — per variant — the residual input block and
    the emitted-z output block.  Stage-1 shapes (~2.5 MB/image at
    32x32x64) fit 4; later stages progressively more.  Each `_run_local`
    call sizes itself (forward and backward invoke this separately with
    their own variant flags), so a backward pass never inherits a
    forward-tuned value unless the caller pinned block_b explicitly."""
    wp = w + 2
    img = h * w * c * 2            # one [block,h,w,c] bf16 block
    per_img = (
        img                        # x block
        + (h + 2) * wp * c * 2     # zp
        + h * wp * 3 * c * 2       # win
        + h * wp * _pad128(3 * c) * 4   # t (f32)
        + h * wp * _pad128(c) * 4       # acc (f32)
        + 3 * img                  # y output + slack (stats tile is tiny)
        + (img if with_res else 0)     # residual input block
        + (img if emit_z else 0)       # emitted z output block
    )
    return max(1, min(32, _VMEM_BUDGET_BYTES // per_img))


def _affine_act(x, scale, shift, res, activate):
    z = x.astype(jnp.float32) * scale + shift
    if res is not None:
        z = z + res.astype(jnp.float32)
    return jnp.maximum(z, 0.0) if activate else z


def _conv_kernel(x_ref, w_ref, scale_ref, shift_ref, y_ref, *, with_res,
                 activate, res_ref=None, z_ref=None, stats_ref=None,
                 valid_b=None):
    # One-matmul conv: rows = (b, h, w') with w' over the padded width,
    # K = (dh, c) built from three H-shifted slices (leading-dim slices —
    # no layout offsets, so the lane concat is legal), N = (dw, o) — all
    # nine taps in a single [rows,192] @ [192,192] MXU contraction. The
    # three dw output column-blocks are then combined by row shifts: a
    # +dw row shift within each 34-row (b,h) group realigns column block
    # dw to its output pixel, and the zero padding of zp supplies SAME
    # semantics. Rows with w' >= w are scratch and sliced off at the end;
    # pltpu.roll's wrapped rows land only there.
    bt, h, w, c = x_ref.shape
    wp = w + 2
    rows = bt * h * wp
    scale = scale_ref[0, :]
    shift = shift_ref[0, :]
    res = res_ref[:] if with_res else None
    zf = _affine_act(x_ref[:], scale, shift, res, activate)
    z = zf.astype(jnp.bfloat16)
    if z_ref is not None:
        # The transformed activation, already resident in VMEM — written out
        # so callers needing it (skip connections) skip a separate
        # read-modify-write pass over HBM.
        z_ref[:] = z.astype(z_ref.dtype)
    zp = jnp.pad(z, ((0, 0), (1, 1), (1, 1), (0, 0)))
    win = jnp.concatenate(
        [zp[:, dh:dh + h, :, :] for dh in range(3)], axis=-1
    ).reshape(rows, 3 * c)
    t = jax.lax.dot_general(
        win, w_ref[:],
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    acc = t[:, 0:c]
    for dw in (1, 2):
        acc = acc + pltpu.roll(t, rows - dw, 0)[:, dw * c:(dw + 1) * c]
    yq = (acc.reshape(bt, h, wp, c)[:, :, 0:w, :]
          .astype(jnp.bfloat16))
    y_ref[:] = yq.astype(y_ref.dtype)
    if stats_ref is not None:
        # Per-channel [sum, sum-of-squares] of the rounded output — the
        # moments BatchNorm needs — accumulated across grid steps while the
        # tile is still in VMEM, so no later stats pass re-reads y from HBM.
        # Batch-pad images (rows >= valid_b) are masked out: they are conv
        # outputs of zero images, which are NOT zero (shift/ReLU/conv).
        i = pl.program_id(0)
        yf = yq.astype(jnp.float32)
        row = jax.lax.broadcasted_iota(jnp.int32, yf.shape, 0)
        keep = (row + i * bt < valid_b).astype(jnp.float32)
        yf = yf * keep
        tile = jnp.stack([jnp.sum(yf, axis=(0, 1, 2)),
                          jnp.sum(jnp.square(yf), axis=(0, 1, 2))])

        @pl.when(i == 0)
        def _():
            stats_ref[:] = tile

        @pl.when(i != 0)
        def _():
            stats_ref[:] = stats_ref[:] + tile


def _stats_of(y):
    """[sum, sum_sq] per channel of a (rounded) conv output, in f32."""
    yf = y.astype(jnp.float32)
    return jnp.stack([jnp.sum(yf, axis=(0, 1, 2)),
                      jnp.sum(jnp.square(yf), axis=(0, 1, 2))])


def _run_local(x, w, scale, shift, residual, block_b, activate,
               emit_z=False, emit_stats=False):
    """Run the kernel on (process-/shard-)local arrays."""
    if _shard_map_interp(x):
        # shard_map + interpret mode (CPU tests): Pallas interpret lowers to
        # a grid scan whose internal index scalars are vma-unvarying, which
        # check_vma rejects. Run the numerically-identical XLA statement
        # (same f32 affine, same bf16 rounding) per shard instead; the
        # kernel body itself is covered by the GSPMD/single-device tests,
        # and on TPU the real (non-interpret) kernel runs under shard_map.
        y = reference_affine_relu_conv(x, w, scale, shift, residual, activate)
        out = [y]
        if emit_z:
            z = _reference_z(x, scale, shift, residual, activate)
            out.append(z.astype(jnp.bfloat16).astype(x.dtype))
        if emit_stats:
            out.append(_stats_of(y.astype(jnp.bfloat16)))
        return tuple(out) if len(out) > 1 else y
    b, h, wd, c = x.shape
    if w.shape != (3, 3, c, c):
        raise ValueError(f"square 3x3 conv only, got weight {w.shape} "
                         f"for input channels {c}")
    if not block_b:
        block_b = min(b, _auto_block_b(h, wd, c, with_res=residual is not None,
                                       emit_z=emit_z))
    xp = _pad_batch(x, block_b)
    # Wcat[(dh, c_in), (dw, c_out)] = w[dh, dw, c_in, c_out]: K rows match
    # the kernel's dh-concat of input slices, N columns put all three dw
    # taps in one contraction.
    w3 = w.astype(jnp.bfloat16).transpose(0, 2, 1, 3).reshape(3 * c, 3 * c)
    scale2 = scale.astype(jnp.float32).reshape(1, c)
    shift2 = shift.astype(jnp.float32).reshape(1, c)
    img_spec = pl.BlockSpec((block_b, h, wd, c), lambda i: (i, 0, 0, 0),
                            memory_space=pltpu.VMEM)
    w_spec = pl.BlockSpec((3 * c, 3 * c), lambda i: (0, 0),
                          memory_space=pltpu.VMEM)
    vec_spec = pl.BlockSpec((1, c), lambda i: (0, 0),
                            memory_space=pltpu.VMEM)
    grid = (xp.shape[0] // block_b,)
    # Inside shard_map, avals carry the mesh axes they vary over (vma) and
    # check_vma requires the pallas out_shape to declare them: the output
    # varies over whatever the operands vary over (vma=frozenset() is
    # equivalent to not passing it).
    operands = (xp, w3, scale2, shift2) + (
        () if residual is None else (residual,))
    img_shape = _shape_struct(xp.shape, x.dtype, *operands)
    out_shape = [img_shape]
    out_specs = [img_spec]
    if emit_z:
        out_shape.append(img_shape)
        out_specs.append(img_spec)
    if emit_stats:
        out_shape.append(_shape_struct((2, c), jnp.float32, *operands))
        out_specs.append(pl.BlockSpec((2, c), lambda i: (0, 0),
                                      memory_space=pltpu.VMEM))
    single_out = len(out_shape) == 1
    with_res = residual is not None

    def body(x_ref, w_ref, sc_ref, sh_ref, *rest):
        res_ref = rest[0] if with_res else None
        outs = rest[1:] if with_res else rest
        y_ref = outs[0]
        z_ref = outs[1] if emit_z else None
        stats_ref = outs[-1] if emit_stats else None
        _conv_kernel(x_ref, w_ref, sc_ref, sh_ref, y_ref, with_res=with_res,
                     activate=activate, res_ref=res_ref, z_ref=z_ref,
                     stats_ref=stats_ref, valid_b=b)

    in_specs = [img_spec, w_spec, vec_spec, vec_spec]
    args = [xp, w3, scale2, shift2]
    if with_res:
        in_specs.append(img_spec)
        args.append(_pad_batch(residual, block_b))
    out = pl.pallas_call(
        body,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs[0] if single_out else out_specs,
        out_shape=out_shape[0] if single_out else out_shape,
        interpret=_interpret(),
    )(*args)
    if single_out:
        return out[:b]
    outs = [out[0][:b]]
    if emit_z:
        outs.append(out[1][:b])
    if emit_stats:
        outs.append(out[-1])
    return tuple(outs)


# --- GSPMD partitioning: shard the batch dim, run the kernel per shard ---

def _make_cp(with_res, emit_z=False, emit_stats=False):
    if with_res:
        def f(x, w, scale, shift, residual, block_b, activate):
            return _run_local(x, w, scale, shift, residual, block_b, activate,
                              emit_z, emit_stats)
        static = (5, 6)
    else:
        def f(x, w, scale, shift, block_b, activate):
            return _run_local(x, w, scale, shift, None, block_b, activate,
                              emit_z, emit_stats)
        static = (4, 5)
    cp = custom_partitioning(f, static_argnums=static)
    multi = emit_z or emit_stats

    def _out_shardings(mesh, batch):
        img = NamedSharding(mesh, P(batch, None, None, None))
        outs = [img]
        if emit_z:
            outs.append(img)
        if emit_stats:
            # Stats are per-channel sums over the *global* batch: the lower
            # fn all-reduces the per-shard partials, so the output is
            # replicated.
            outs.append(NamedSharding(mesh, P(None, None)))
        return tuple(outs) if multi else img

    def infer(*cb_args):
        mesh, arg_infos, _ = cb_args[-3:]
        return _out_shardings(mesh, _batch_axis(arg_infos))

    def part(*cb_args):
        block_b, activate = cb_args[:2]
        mesh, arg_infos, _ = cb_args[-3:]
        batch = _batch_axis(arg_infos)
        img = NamedSharding(mesh, P(batch, None, None, None))
        rep1 = NamedSharding(mesh, P(None))
        arg_shardings = (img, NamedSharding(mesh, P(None, None, None, None)),
                         rep1, rep1) + ((img,) if with_res else ())

        def lower(x, w, scale, shift, residual=None):
            out = _run_local(x, w, scale, shift, residual, block_b, activate,
                             emit_z, emit_stats)
            if emit_stats and batch is not None:
                # Per-shard partial sums -> global sums over whatever axis
                # the partitioner sharded the batch on (not necessarily
                # DATA_AXIS — this is mesh-generic lowering code).
                out = out[:-1] + (jax.lax.psum(out[-1], batch),)  # dplint: allow(DP103)
            return out

        if with_res:
            lower_fn = lower
        else:
            def lower_fn(x, w, scale, shift):
                return lower(x, w, scale, shift)
        return mesh, lower_fn, _out_shardings(mesh, batch), arg_shardings

    # Shardy mini-language: only the batch factor `b` is shared (x, residual,
    # outputs), so batch sharding propagates and nothing else does.
    ins = ("b h w c, p q i o, e, g, b r s t" if with_res
           else "b h w c, p q i o, e, g")
    outs = ["b h w c"]
    if emit_z:
        outs.append("b h w c")
    if emit_stats:
        outs.append("u v")  # fresh factors: stats are replicated, never
        # tied to the channel factor (the partition rule psums partials)
    cp.def_partition(partition=part, infer_sharding_from_operands=infer,
                     sharding_rule=f"{ins} -> {', '.join(outs)}")
    return cp


_CPS = {
    (with_res, emit_z, emit_stats): _make_cp(with_res, emit_z, emit_stats)
    for with_res in (False, True)
    for emit_z in (False, True)
    for emit_stats in (False, True)
}


def _run_fused_conv(x, w, scale, shift, residual, block_b, activate,
                    emit_z=False, emit_stats=False):
    cp = _CPS[(residual is not None, emit_z, emit_stats)]
    if residual is not None:
        return cp(x, w, scale, shift, residual, block_b, activate)
    return cp(x, w, scale, shift, block_b, activate)


def _reference_z(x, scale, shift, residual, activate=True):
    return _affine_act(x, scale.astype(jnp.float32),
                       shift.astype(jnp.float32), residual, activate)


def _conv3x3(z, w):
    # bf16 operands, bf16 output — the statement Flax's nn.Conv(dtype=bf16)
    # makes (no preferred_element_type: its conv transpose can't mix a f32
    # cotangent with bf16 operands on this jax). The MXU accumulates in f32
    # internally either way; the Pallas kernel keeps its f32 VMEM
    # accumulator and rounds through bf16 on the final write to match this
    # statement bit-for-bit.
    return jax.lax.conv_general_dilated(
        z.astype(jnp.bfloat16), w.astype(jnp.bfloat16),
        window_strides=(1, 1), padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def _fused_conv_vjp(x, w, scale, shift, residual, block_b, activate,
                    pallas_bwd, emit_z, emit_stats):
    return _run_fused_conv(x, w, scale, shift, residual, block_b, activate,
                           emit_z, emit_stats)


def _fwd_rule(x, w, scale, shift, residual, block_b, activate, pallas_bwd,
              emit_z, emit_stats):
    out = _run_fused_conv(x, w, scale, shift, residual, block_b, activate,
                          emit_z, emit_stats)
    y = out[0] if (emit_z or emit_stats) else out
    # y is saved only for the stats backward (it already exists in HBM —
    # no extra memory or recompute).
    return out, (x, w, scale, shift, residual, y if emit_stats else None)


def _bwd_core(block_b, activate, pallas_bwd, residuals, ct, ct_z=None):
    # Recompute z (cheap elementwise, fuses into the grad convs) instead of
    # saving it. The weight-grad contraction is XLA's (efficient per the
    # profile); the input-grad conv is XLA's conv-transpose by default, or
    # this kernel with flipped weights when pallas_bwd — identical math:
    # conv_transpose(ct, w) == conv3x3(ct, flip_hw(w).swap_io()) at
    # stride 1 / SAME. ct_z (emit variant) is the cotangent of the
    # emitted activation; it joins the conv's input-grad at z.
    x, w, scale, shift, residual = residuals
    z = _reference_z(x, scale, shift, residual, activate)
    # _conv3x3's primal output is bf16; the forward's final cast to x.dtype
    # transposes to this cast of the incoming cotangent.
    ctc = ct.astype(jnp.bfloat16)
    if pallas_bwd:
        # w-only vjp: no XLA dz path exists to depend on jit DCE.
        dw = jax.vjp(lambda wi: _conv3x3(z, wi), w)[1](ctc)[0]
        w_flip = jnp.flip(w, axis=(0, 1)).transpose(0, 1, 3, 2)
        ones = jnp.ones((x.shape[-1],), jnp.float32)
        zeros = jnp.zeros((x.shape[-1],), jnp.float32)
        dz = _run_fused_conv(ctc, w_flip, ones, zeros, None, block_b,
                             False).astype(jnp.float32)
    else:
        dz, dw = jax.vjp(_conv3x3, z, w)[1](ctc)
        dz = dz.astype(jnp.float32)
    if ct_z is not None:
        dz = dz + ct_z.astype(jnp.float32)
    # Through act and affine: gate on the post-act sign (z>0 iff pre>0).
    dpre = dz * (z > 0) if activate else dz
    dx = (dpre * scale.astype(jnp.float32)).astype(x.dtype)
    dscale = jnp.sum(dpre * x.astype(jnp.float32),
                     axis=(0, 1, 2)).astype(scale.dtype)
    dshift = jnp.sum(dpre, axis=(0, 1, 2)).astype(shift.dtype)
    dres = dpre.astype(residual.dtype) if residual is not None else None
    return dx, dw, dscale, dshift, dres


def _bwd_rule(block_b, activate, pallas_bwd, emit_z, emit_stats, residuals,
              cts):
    *core_res, y = residuals
    ct_list = list(cts) if (emit_z or emit_stats) else [cts]
    ct_y = ct_list[0]
    ct_z = ct_list[1] if emit_z else None
    if emit_stats:
        # stats = [sum(yq), sum(yq^2)]: their cotangent joins y's before the
        # conv backward (summed in f32, rounded once into the bf16 ct).
        ct_stats = ct_list[-1]
        yf = y.astype(jnp.float32)
        ct_y = (ct_y.astype(jnp.float32)
                + ct_stats[0][None, None, None, :]
                + 2.0 * yf * ct_stats[1][None, None, None, :])
    return _bwd_core(block_b, activate, pallas_bwd, tuple(core_res), ct_y,
                     ct_z)


_fused_conv_vjp.defvjp(_fwd_rule, _bwd_rule)


def fused_affine_relu_conv(x, w, scale, shift, residual, block_b=_BLOCK_B,
                           activate=True, pallas_bwd=False):
    """y = conv3x3_SAME(act(x*scale + shift [+ residual]), w), fused on TPU.

    x: [B,H,W,C] (any float dtype; affine computed in f32, conv in bf16),
    w: [3,3,C,C], scale/shift: [C], residual: [B,H,W,C] or None;
    act = ReLU when `activate` else identity. Returns y with x's dtype.
    Differentiable in x, w, scale, shift, residual. Batch-sharded under a
    mesh (custom partitioning); block_b is the per-grid-step image count.
    `pallas_bwd` routes the backward input-grad conv (the same 3x3
    stride-1 C->C shape, spatially-flipped io-swapped weights) through
    this kernel too; the weight-grad contraction stays on XLA either way.
    """
    return _fused_conv_vjp(x, w, scale, shift, residual, block_b, activate,
                           pallas_bwd, False, False)


def fused_affine_relu_conv_emit(x, w, scale, shift, residual,
                                block_b=_BLOCK_B, activate=True,
                                pallas_bwd=False):
    """Like `fused_affine_relu_conv`, but also returns the transformed
    activation z = act(x*scale + shift [+ residual]) as a second output,
    written from VMEM in the same kernel pass — callers that need it (skip
    connections) avoid a separate read-modify-write over HBM."""
    return _fused_conv_vjp(x, w, scale, shift, residual, block_b, activate,
                           pallas_bwd, True, False)


def fused_conv_bn(x, w, scale, shift, residual, block_b=_BLOCK_B,
                  activate=True, pallas_bwd=False, emit_z=False):
    """Fused conv that also emits BatchNorm moments of its output.

    Returns ``(y, [z,] stats)`` where ``stats`` is the per-channel
    ``[sum(y), sum(y^2)]`` (f32), accumulated in VMEM while each tile is
    produced — the moments `BatchNormCoeffs` needs, without the separate
    XLA reduction pass that would re-read y from HBM (batch-pad images are
    masked out). Under a sharded mesh the partition rule all-reduces the
    per-shard partials, so stats are global sums (sync-BN); under
    shard_map they are the shard's partials, to be `pmean`'d by the
    caller via ``axis_name`` — the same split the unfused BatchNorm has.
    """
    return _fused_conv_vjp(x, w, scale, shift, residual, block_b, activate,
                           pallas_bwd, emit_z, True)


def reference_affine_relu_conv(x, w, scale, shift, residual=None,
                               activate=True):
    """Unfused XLA statement of the same math (oracle for tests/benches)."""
    z = _reference_z(x, scale, shift, residual, activate)
    return _conv3x3(z, w).astype(x.dtype)
