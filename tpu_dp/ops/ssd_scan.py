"""The selective state-space scan (Mamba-2's SSD) in its chunked form: a
Pallas TPU kernel pair under a `jax.custom_vjp` that keeps a chunk's decay
matrix, its ``C B^T`` and the carried state in fast memory.

With ``S_t = exp(delta_t a) S_{t-1} + delta_t x_t (outer) b_t`` from ``S_{-1}
= 0`` a head, the result is ``y_t = S_t c_t + D x_t``: what
`tpu_dp.models.nemotron_h.ssd_chunked` computes (and a skip term), the same
sums at the same precision, in chunks of `CHUNK` = 128 positions::

    cum_i = sum_{j <= i} delta_j a               (within the chunk)
    w_ij  = (c_i . b_j) exp(cum_i - cum_j) delta_j          (j <= i)
    y     = w x + exp(cum) (c S_in^T) + D x
    S_out = exp(cum_last) S_in + (exp(cum_last - cum) delta x)^T b

Decays, cumulative sums, the carried state and every accumulator are
float32; ``w``, the weighted ``x`` and the entering state are rounded to the
operands' dtype for their products, as `ssd_chunked` rounds them, and
nothing else is.

**The grid** is ``(rows, groups, chunks)`` with the chunks of a row the
innermost, sequential axis: a step holds a chunk of one group's heads (eight
at the published widths), so ``C B^T`` is one product for all of them and
``dB``, ``dC`` add up over them inside the step. The state ``[state, heads of
the group x head_dim]`` lives in scratch along the chunk axis; the backward
kernel walks the chunks in reverse and carries the state's gradient the same
way. The operands are read as the layer has them (``x [rows, L, inner]``,
``b, c [rows, L, groups * state]``); ``y`` is written, and its cotangent
read, a group at a time (``[rows, groups, L, inner / groups]`` float32: the
layer's grouped norm reduces over the last axis as it stands, where
``[rows, L, inner]`` cost it a relayout of the whole array, 21 ms a step at
the published widths); ``delta`` goes in heads-major (``[rows, heads, L]``,
2 MB a row at the published widths: a chunk of a group's heads is one vector
register, and its cumulative sum seven lane rolls). Nothing of the size
``[L, heads, 128]`` float32 exists outside a step: per head a step makes the
``128 x 128`` decay matrix from the two orientations of ``cum`` (one
transpose a step), uses it and drops it. What is per head and position is
spread over a head's lanes once a step (`_wide`) so that the state's
products, its update, the skip and ``y``'s assembly are whole-group
operations. Heads narrower than 128 lanes
share a 128-lane piece: each head's product is taken over the piece and its
own lanes are selected, so no slice cuts a register.

**Forward** under differentiation also writes the state each chunk entered
with (``[rows, chunks, state, inner]`` float32, 134 MB a row at the
published widths, alive during that layer's backward only). **Backward**,
one kernel: the forward's chunk terms again, then ``dx``, ``dB``, ``dC``,
``d delta`` and the partial sums of ``d a`` and ``d D`` (a register row a
group, summed outside). The cotangents are rounded to the operands' dtype
for their products, which is what the compiler's default precision does to
the float32 cotangents of `ssd_chunked`'s products.

Both kernels carry one operation name, `NAME` (the benchmark's
`ssd_scan_roofline` reads the prefix ``ssd_scan``; apart, one of two might
not rank among a traced step's ten longest operations). They compile for
the TPU; inside `tpu_dp.ops.interpret_kernels()` the same code runs in the
Pallas interpreter, which is how the tests exercise it on the CPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tpu_dp.ops import _partition
from tpu_dp.ops._partition import interpret as _interpret
from tpu_dp.ops._partition import shape_struct as _shape_struct

F32 = jnp.float32
CHUNK = 128         # positions a chunk: the published one, and a lane tile
LANES = 128
NAME = "ssd_scan_pair"
_NEG = -1e30        # exp(_NEG) is 0: the pairs above the diagonal
_NT = (((1,), (1,)), ((), ()))      # a b^T
_TN = (((0,), (0,)), ((), ()))      # a^T b


def fits(length: int, chunk: int, heads: int, head_dim: int, groups: int,
         state: int) -> bool:
    """Whether the kernels take these shapes: whole chunks of `CHUNK` in a
    row, a state of whole 128 lanes, a group's heads times ``head_dim``
    whole 128 lanes (heads that divide a 128-lane piece, or are whole
    ones), and a group's heads whole sublane tiles of eight."""
    if groups < 1 or heads % groups:
        return False
    r = heads // groups
    piece = max(head_dim, LANES)
    return (chunk == CHUNK and length % CHUNK == 0 and length > 0
            and state % LANES == 0 and head_dim % 8 == 0
            and (LANES % head_dim == 0 or head_dim % LANES == 0)
            and (r * head_dim) % piece == 0
            and r % 8 == 0 and 2 * r <= LANES)


def runs(length: int, chunk: int, heads: int, head_dim: int, groups: int,
         state: int) -> bool:
    """Whether the scan goes through its kernels: the shapes fit and the
    kernels can run here (on a TPU, or inside `interpret_kernels()`)."""
    return (fits(length, chunk, heads, head_dim, groups, state)
            and _partition.kernels_can_run())


# ------------------------------------------------------- inside the kernels

def _iota(shape, axis):
    return jax.lax.broadcasted_iota(jnp.int32, shape, axis)


def _cumsum_lanes(v, reverse=False):
    """The running sum of ``v [k, q]`` along the lanes (from the last lane
    down if ``reverse``), by doubling: log2(q) rolls."""
    q = v.shape[1]
    lane = _iota(v.shape, 1)
    shift = 1
    while shift < q:
        if reverse:
            v = v + jnp.where(lane < q - shift,
                              pltpu.roll(v, q - shift, 1), 0.0)
        else:
            v = v + jnp.where(lane >= shift, pltpu.roll(v, shift, 1), 0.0)
        shift *= 2
    return v


def _columns(rows):
    """``rows [k, q]`` as the first ``k`` columns of ``[q, 128]``."""
    k, q = rows.shape
    return jnp.concatenate(
        [rows, jnp.zeros((LANES - k, q), rows.dtype)], axis=0).T


def _pieces(p):
    """``(lanes a piece, heads a piece)``: a head of ``p`` lanes or more is
    a piece, narrower ones share a piece of 128."""
    piece = max(p, LANES)
    return piece, piece // p


def _own(v, t, p, per):
    """``v [q, piece]`` with the lanes of the piece's other heads zeroed:
    those of its head ``t`` of ``per``."""
    if per == 1:
        return v
    lane = _iota(v.shape, 1)
    return jnp.where((lane >= t * p) & (lane < (t + 1) * p), v,
                     jnp.zeros_like(v))


def _join(pieces, p):
    """A piece's heads' results ``[q, piece]`` each, every head's own lanes
    taken from its own, and the pieces side by side."""
    _, per = _pieces(p)
    lane = _iota(pieces[0].shape, 1)
    out = []
    for k in range(0, len(pieces), per):
        got = pieces[k]
        for t in range(1, per):
            got = jnp.where(lane >= t * p, pieces[k + t], got)
        out.append(got)
    return out[0] if len(out) == 1 else jnp.concatenate(out, axis=1)


def _wide(cols, first, heads, p):
    """``[q, heads * p]``: the lanes of head ``h`` hold column ``first + h``
    of ``cols [q, 128]``."""
    piece, _ = _pieces(p)
    return _join([jnp.broadcast_to(cols[:, first + h:first + h + 1],
                                   (cols.shape[0], piece))
                  for h in range(heads)], p)


def _head_sums(v, heads, p):
    """The sums of ``v [q, heads * p]`` over each head's lanes: ``heads``
    columns ``[q, 1]``."""
    piece, per = _pieces(p)
    return [jnp.sum(_own(v[:, h // per * piece:(h // per + 1) * piece],
                         h % per, p, per), axis=1, keepdims=True)
            for h in range(heads)]


def _chunk_terms(a_ref, dlt_ref, p):
    """``(delta, cum, cols, cum_w, dlt_w, last_w)`` of a step: ``delta`` and
    its cumulative sum times ``a`` as ``[heads, q]``, both again as columns
    (``cols [q, 128]``: ``cum`` of head ``h`` at ``h``, ``delta`` at ``heads
    + h``) and spread over the heads' lanes (``[q, heads * p]``), and
    ``cum`` at the chunk's last position ``[1, heads * p]``."""
    dlt = dlt_ref[...]
    r, q = dlt.shape
    cum = _cumsum_lanes(dlt * a_ref[...])
    cols = _columns(jnp.concatenate([cum, dlt], axis=0))
    cum_w, dlt_w = _wide(cols, 0, r, p), _wide(cols, r, r, p)
    return dlt, cum, cols, cum_w, dlt_w, cum_w[q - 1:q]


def _fwd_kernel(a_ref, d_ref, dlt_ref, x_ref, b_ref, c_ref, y_ref, *rest, p):
    """``rest``: the result for the states the chunks entered with, where
    they are saved, and the scratch that carries the state."""
    state = rest[-1]

    @pl.when(pl.program_id(2) == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    q, width = x_ref.shape
    dtype = x_ref.dtype
    dlt, cum, cols, cum_w, dlt_w, last_w = _chunk_terms(a_ref, dlt_ref, p)
    x, b, c = x_ref[...], b_ref[...], c_ref[...]
    xf = x.astype(F32)
    cb = jax.lax.dot_general(c, b, _NT, preferred_element_type=F32)
    tril = _iota((q, q), 0) >= _iota((q, q), 1)
    piece, per = _pieces(p)
    pieces = []
    for h in range(dlt.shape[0]):
        at = h // per * piece
        seg = cols[:, h:h + 1] - cum[h:h + 1, :]
        w = cb * jnp.exp(jnp.where(tril, seg, _NEG)) * dlt[h:h + 1, :]
        pieces.append(jnp.dot(w.astype(dtype), x[:, at:at + piece],
                              preferred_element_type=F32))
    y = _join(pieces, p)
    entering = state[...]
    if len(rest) == 2:
        rest[0][...] = entering
    y_in = jnp.dot(c, entering.astype(dtype), preferred_element_type=F32)
    y_ref[...] = y + y_in * jnp.exp(cum_w) + d_ref[...] * xf
    xw = (xf * (jnp.exp(last_w - cum_w) * dlt_w)).astype(dtype)
    state[...] = jnp.exp(last_w) * entering + jax.lax.dot_general(
        b, xw, _TN, preferred_element_type=F32)


def _bwd_kernel(a_ref, d_ref, dlt_ref, x_ref, b_ref, c_ref, s_ref, dy_ref,
                dx_ref, db_ref, dc_ref, ddlt_ref, da_ref, dd_ref, dstate,
                *, p):
    @pl.when(pl.program_id(2) == 0)
    def _():
        dstate[...] = jnp.zeros_like(dstate)
        da_ref[...] = jnp.zeros_like(da_ref)
        dd_ref[...] = jnp.zeros_like(dd_ref)

    q, width = x_ref.shape
    r = dlt_ref.shape[0]
    dtype = x_ref.dtype
    dlt, cum, cols, cum_w, dlt_w, last_w = _chunk_terms(a_ref, dlt_ref, p)
    x, b, c = x_ref[...], b_ref[...], c_ref[...]
    xf, dy = x.astype(F32), dy_ref[...]
    dyb = dy.astype(dtype)
    e_w, t_w, decay_w = (jnp.exp(cum_w), jnp.exp(last_w - cum_w),
                         jnp.exp(last_w))
    entering, ds = s_ref[...], dstate[...]
    sb, dsb = entering.astype(dtype), ds.astype(dtype)

    # Through the state: what the chunk read of the state it was handed
    # (y_in = exp(cum) c S^T) and what it added to the one it handed on.
    dxw = jnp.dot(b, dsb, preferred_element_type=F32)
    yc = jnp.dot(c, sb, preferred_element_type=F32)
    dye = (dy * e_w).astype(dtype)
    te_w = t_w * dlt_w
    xw = (xf * te_w).astype(dtype)
    dc = jax.lax.dot_general(dye, sb, _NT, preferred_element_type=F32)
    db = jax.lax.dot_general(xw, dsb, _NT, preferred_element_type=F32)
    dstate[...] = decay_w * ds + jax.lax.dot_general(
        c, dye, _TN, preferred_element_type=F32)
    # d delta and d cum by position and head, over the heads' lanes: of the
    # weights exp(cum_last - cum) delta, of y_in's decay, and at the chunk's
    # last position of the state's own decay and of cum_last in the weights.
    to_delta = dxw * xf * t_w
    moved = dxw * xf * te_w
    to_cum = dy * yc * e_w - moved + jnp.where(
        _iota((q, width), 0) == q - 1,
        jnp.sum(ds * entering, axis=0, keepdims=True) * decay_w
        + jnp.sum(moved, axis=0, keepdims=True), 0.0)

    # Within the chunk, a head at a time.
    cb = jax.lax.dot_general(c, b, _NT, preferred_element_type=F32)
    tril = _iota((q, q), 0) >= _iota((q, q), 1)
    piece, per = _pieces(p)
    dcb = jnp.zeros((q, q), F32)
    pieces, row_sums, col_sums = [], [], []
    for h in range(r):
        at = h // per * piece
        xp, dyp = x[:, at:at + piece], dyb[:, at:at + piece]
        seg = cols[:, h:h + 1] - cum[h:h + 1, :]
        decay = jnp.exp(jnp.where(tril, seg, _NEG))
        w = cb * decay * dlt[h:h + 1, :]
        dw = jax.lax.dot_general(_own(dyp, h % per, p, per), xp, _NT,
                                 preferred_element_type=F32) * decay
        dcb = dcb + dw * dlt[h:h + 1, :]
        g = dw * cb                 # d w / d delta_j, a pair
        col_sums.append(jnp.sum(g, axis=0, keepdims=True))
        row_sums.append(jnp.sum(g * dlt[h:h + 1, :], axis=1, keepdims=True))
        pieces.append(jax.lax.dot_general(w.astype(dtype), dyp, _TN,
                                          preferred_element_type=F32))
    dx = _join(pieces, p)
    dx_ref[...] = (dx + dxw * te_w + d_ref[...] * dy).astype(dtype)
    dcb = dcb.astype(dtype)
    dc_ref[...] = (dc + jnp.dot(dcb, b, preferred_element_type=F32)
                   ).astype(dc_ref.dtype)
    db_ref[...] = (db + jax.lax.dot_general(
        dcb, c, _TN, preferred_element_type=F32)).astype(db_ref.dtype)
    dd_ref[...] += jnp.sum((dy * xf).reshape(q // 8, 8, width), axis=0)

    # The columns turned to rows ``[heads, q]``, the cumulative sum undone.
    placed = jnp.zeros((q, LANES), F32)
    at = _iota((q, LANES), 1)
    for h, (to_c, to_d, rows) in enumerate(zip(
            _head_sums(to_cum, r, p), _head_sums(to_delta, r, p), row_sums)):
        placed = jnp.where(at == h, to_c + rows, placed)
        placed = jnp.where(at == r + h, to_d, placed)
    placed = placed.T
    dcum, ddlt = placed[:r], placed[r:2 * r]
    col_sums = jnp.concatenate(col_sums, axis=0)
    dda = _cumsum_lanes(dcum - col_sums * dlt, reverse=True)
    ddlt_ref[...] = a_ref[...] * dda + col_sums + ddlt
    da_ref[...] += dlt * dda


# ----------------------------------------------------------------- the calls

def _specs(dlt, x, b, groups, reverse):
    """The grid ``(rows, groups, chunks)`` and the specs of: ``a``, ``D``
    over the lanes, a chunk of a group's ``delta``, of its ``x``, of its
    ``b`` or ``c``, the state a chunk entered with, and a chunk of a
    group's ``y``."""
    rows, heads, length = dlt.shape
    inner, n = x.shape[-1], b.shape[-1] // groups
    r = heads // groups
    width, nc = inner // groups, length // CHUNK
    vmem = pltpu.VMEM

    def at(ci):
        return nc - 1 - ci if reverse else ci

    return (rows, groups, nc), (
        pl.BlockSpec((r, 1), lambda i, g, ci: (g, 0), memory_space=vmem),
        pl.BlockSpec((1, width), lambda i, g, ci: (0, g), memory_space=vmem),
        pl.BlockSpec((None, r, CHUNK), lambda i, g, ci: (i, g, at(ci)),
                     memory_space=vmem),
        pl.BlockSpec((None, CHUNK, width), lambda i, g, ci: (i, at(ci), g),
                     memory_space=vmem),
        pl.BlockSpec((None, CHUNK, n), lambda i, g, ci: (i, at(ci), g),
                     memory_space=vmem),
        pl.BlockSpec((None, None, n, width),
                     lambda i, g, ci: (i, at(ci), 0, g), memory_space=vmem),
        pl.BlockSpec((None, None, CHUNK, width),
                     lambda i, g, ci: (i, g, at(ci), 0), memory_space=vmem))


_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"))


@functools.partial(jax.jit, static_argnames=("groups", "save", "interpret"))
def _forward(a, d, dlt, x, b, c, groups, save, interpret):
    """``y``, and with ``save`` the state each chunk entered with. (Under
    `jax.jit`, here and on `_backward`, so that the layers of a model trace
    a kernel's body once between them; ``interpret`` is `_interpret()` at
    the caller's, a static argument so that it is part of that cache's key.)"""
    rows, heads, length = dlt.shape
    inner, n = x.shape[-1], b.shape[-1] // groups
    grid, (by_a, by_d, by_dlt, by_x, by_bc, by_state, by_y) = _specs(
        dlt, x, b, groups, reverse=False)
    operands = (a, d, dlt, x, b, c)
    out_shape = [_shape_struct((rows, groups, length, inner // groups), F32,
                               *operands)]
    out_specs = [by_y]
    if save:
        out_shape.append(_shape_struct(
            (rows, length // CHUNK, n, inner), F32, *operands))
        out_specs.append(by_state)
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, p=inner // heads),
        grid=grid, in_specs=[by_a, by_d, by_dlt, by_x, by_bc, by_bc],
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((n, inner // groups), F32)],
        compiler_params=_PARAMS, interpret=interpret, name=NAME,
    )(*operands)
    return out if save else out[0]


@functools.partial(jax.jit, static_argnames=("groups", "interpret"))
def _backward(a, d, dlt, x, b, c, states, dy, groups, interpret):
    rows, heads, length = dlt.shape
    inner, n = x.shape[-1], b.shape[-1] // groups
    r, width = heads // groups, inner // groups
    grid, (by_a, by_d, by_dlt, by_x, by_bc, by_state, by_y) = _specs(
        dlt, x, b, groups, reverse=True)
    operands = (a, d, dlt, x, b, c, states, dy)
    vmem = pltpu.VMEM
    return pl.pallas_call(
        functools.partial(_bwd_kernel, p=inner // heads),
        grid=grid,
        in_specs=[by_a, by_d, by_dlt, by_x, by_bc, by_bc, by_state, by_y],
        out_specs=(
            by_x, by_bc, by_bc, by_dlt,
            pl.BlockSpec((None, r, LANES), lambda i, g, ci: (i, g, 0),
                         memory_space=vmem),
            pl.BlockSpec((None, 8, width), lambda i, g, ci: (i, 0, g),
                         memory_space=vmem)),
        out_shape=(
            _shape_struct(x.shape, x.dtype, *operands),
            _shape_struct(b.shape, b.dtype, *operands),
            _shape_struct(c.shape, c.dtype, *operands),
            _shape_struct(dlt.shape, F32, *operands),
            _shape_struct((rows, heads, LANES), F32, *operands),
            _shape_struct((rows, 8, inner), F32, *operands)),
        scratch_shapes=[pltpu.VMEM((n, width), F32)],
        compiler_params=_PARAMS, interpret=interpret, name=NAME,
    )(*operands)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _scan(a, d, dlt, x, b, c, groups):
    return _forward(a, d, dlt, x, b, c, groups, save=False,
                    interpret=_interpret())


def _fwd_rule(a, d, dlt, x, b, c, groups):
    y, states = _forward(a, d, dlt, x, b, c, groups, save=True,
                         interpret=_interpret())
    return y, (a, d, dlt, x, b, c, states)


def _bwd_rule(groups, residuals, dy):
    dx, db, dc, ddlt, da, dd = _backward(*residuals, dy, groups,
                                         interpret=_interpret())
    return (jnp.sum(da, axis=(0, 2))[:, None],
            jnp.sum(dd, axis=(0, 1))[None], ddlt, dx, db, dc)


_scan.defvjp(_fwd_rule, _bwd_rule)


def ssd_scan(x, delta, a_head, b, c, d_skip, groups: int):
    """``y_t = S_t c_t + D x_t`` of the recurrence above, a group at a time:
    ``[rows, groups, L, inner / groups]`` float32. ``x [rows, L, inner]``
    (``heads`` heads of ``inner / heads``) and ``b, c [rows, L, groups *
    state]`` (head ``h`` reads group ``h // (heads / groups)``) in the
    compute dtype, ``delta [rows, L, heads]``, ``a_head [heads]`` (negative)
    and ``d_skip [heads]`` float32. Differentiable in all six. The shapes
    are `fits`' to allow."""
    heads = delta.shape[-1]
    return _scan(a_head.astype(F32)[:, None],
                 jnp.repeat(d_skip.astype(F32), x.shape[-1] // heads)[None],
                 jnp.swapaxes(delta.astype(F32), 1, 2), x, b, c, groups)
