"""The Pallas kernels compile for a described (not attached) v5e chip.

Interpret mode — what every other test of `tpu_dp.ops` runs — cannot see a
misaligned slice, an over-budget VMEM tile or an op Mosaic refuses; the
TPU's compiler can, and it is installed here. Each case lowers one kernel
at a shape `chip_smoke.py` runs on the chip, compiles it for one device of
a described ``v5e:2x2`` topology, and asserts the compiled program holds a
``tpu_custom_call``. Two tests ask the opposite kind of thing of the
step's own code, which only the TPU's compiler can say: that it makes no
loop of the random crop (`tpu_dp.data.augment`), and that it copies no
array the size of the data set to gather a batch from the resident feed
(`tpu_dp.train.step.gather_rows`). Nothing runs, so nothing here is a
result or a time.

One file and in-process on purpose: only one process at a time may load
the TPU's library, so the topology is described inside a module-scoped
fixture (never at import, in a `skipif` or in `parametrize` arguments) and
no test starts a child.
"""

from __future__ import annotations

import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from tpu_dp.data.augment import make_augment_fn
from tpu_dp.ops import _partition, conv_block, xent


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # whatever keeps the compiler from loading
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def compiled_kernels(monkeypatch):
    """The kernels as the chip gets them: no interpret request (conftest's
    is suspended), the backend check answered as on the chip, and the
    persistent compile cache off (an entry compiled for a described device
    cannot be read back without one)."""
    from jax.experimental.compilation_cache import compilation_cache

    monkeypatch.setattr(_partition, "_interpret_requests", 0)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text, "no Mosaic kernel in the program"
    return compiled


def _xent_fwd(logits, labels):
    return xent.softmax_xent(logits, labels)


def _xent_bwd(logits, labels):
    return jax.grad(lambda lg: jnp.sum(xent.softmax_xent(lg, labels)))(logits)


@pytest.mark.parametrize("fn", [_xent_fwd, _xent_bwd],
                         ids=["fwd", "bwd"])
@pytest.mark.parametrize("batch,classes", [(2048, 10), (1024, 100)])
def test_xent_compiles_for_v5e(one_chip, compiled_kernels, fn, batch,
                               classes):
    _compile(fn, one_chip,
             ((batch, classes), jnp.bfloat16), ((batch,), jnp.int32))


_X = ((256, 32, 32, 64), jnp.bfloat16)
_W = ((3, 3, 64, 64), jnp.bfloat16)
_C = ((64,), jnp.float32)


def _conv_plain(x, w, scale, shift):
    return conv_block.fused_affine_relu_conv(x, w, scale, shift, None)


def _conv_full(x, w, scale, shift, res):
    return conv_block.fused_conv_bn(x, w, scale, shift, res, emit_z=True)


def _conv_plain_bwd(x, w, scale, shift):
    def loss(x, w):
        y = conv_block.fused_affine_relu_conv(x, w, scale, shift, None,
                                              pallas_bwd=True)
        return jnp.sum(y.astype(jnp.float32))
    return jax.grad(loss, argnums=(0, 1))(x, w)


def _conv_full_bwd(x, w, scale, shift, res):
    def loss(x, w):
        y, z, stats = conv_block.fused_conv_bn(x, w, scale, shift, res,
                                               emit_z=True)
        return (jnp.sum(y.astype(jnp.float32))
                + jnp.sum(z.astype(jnp.float32)) + jnp.sum(stats))
    return jax.grad(loss, argnums=(0, 1))(x, w)


@pytest.mark.parametrize("fn,shapes", [
    (_conv_plain, (_X, _W, _C, _C)),
    (_conv_full, (_X, _W, _C, _C, _X)),
    (_conv_plain_bwd, (_X, _W, _C, _C)),
    (_conv_full_bwd, (_X, _W, _C, _C, _X)),
], ids=["plain", "residual+z+stats", "plain-bwd", "residual+z+stats-bwd"])
def test_conv_block_compiles_for_v5e(one_chip, compiled_kernels, fn, shapes):
    compiled = _compile(fn, one_chip, *shapes)
    # One (256,32,32,64) bf16 image block is 32 MiB; the whole call must
    # sit far inside the chip's 16 GB.
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes \
        + mem.output_size_in_bytes < 2 * 2**30


def _crop_accumulated(step, images):
    aug, k = make_augment_fn(1), images.shape[0]
    return jax.vmap(lambda i, im: aug(step * k + i, im))(jnp.arange(k),
                                                         images)


@pytest.mark.parametrize("fn,shape", [
    (make_augment_fn(1), (4096, 32, 32, 3)),
    (_crop_accumulated, (2, 2048, 32, 32, 3)),
], ids=["batch", "microbatches"])
def test_random_crop_is_no_loop_for_v5e(one_chip, compiled_kernels, fn,
                                        shape):
    """A per-image `dynamic_slice` is a `gather` that the v5e compiler runs
    as a `while` of one trip an image (74 ms of a 204 ms step, PERF.md §6,
    PR 26). The crop as shipped selects among static shifts of the whole
    batch: no loop, no gather, no dynamic slice in the TPU's program."""
    args = [jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip),
            jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert " select(" in text
    for kind in ("while", "gather", "dynamic-slice", "dynamic-update-slice"):
        assert f" {kind}(" not in text, kind


def _first_layer_of(gather):
    """A batch out of the resident data set as the images go: the gather,
    the step's normalisation, one 3x3 convolution in bfloat16."""
    from tpu_dp.train.step import _maybe_normalize

    def fn(data, idx, w):
        x = _maybe_normalize(gather(data, idx)).astype(jnp.bfloat16)
        return jax.lax.conv_general_dilated(
            x, w, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"))
    return fn


def _moves_over(text: str, rows: int) -> list[str]:
    """The `copy` and `transpose` ops of a compiled program that have
    ``rows`` among the dimensions of their result."""
    return [m.group(0) for m in re.finditer(
        r"= \w+\[([\d,]*)\]\S* (?:copy|transpose)\(", text)
        if str(rows) in m.group(1).split(",")]


@pytest.mark.parametrize("rows,batch", [(131072, 4096), (32768, 1024)])
def test_resident_gather_copies_no_data_set_for_v5e(one_chip,
                                                    compiled_kernels, rows,
                                                    batch):
    """A ``uint8[N, 32, 32, 3]`` argument's default layout on the v5e is
    N-minor, and to gather rows along the minor dimension the compiler
    first relays the whole array out, on every call: ``copy(%data)`` over
    ``u8[131072,32,32,3]``, 10 ms of a 129 ms step (PERF.md §6, PR 29).
    Staged with its rows flat (`DataPipeline.resident_data`) the gather
    reads the argument in place. The 4-D staging stays here as the
    yardstick: the same question finds its copy."""
    from tpu_dp.train.step import gather_rows

    sample = (32, 32, 3)
    idx = jax.ShapeDtypeStruct((batch,), jnp.int32, sharding=one_chip)
    w = jax.ShapeDtypeStruct((3, 3, 3, 64), jnp.bfloat16, sharding=one_chip)

    def compiled_text(gather, data_shape):
        data = jax.ShapeDtypeStruct(data_shape, jnp.uint8, sharding=one_chip)
        return jax.jit(_first_layer_of(gather)).lower(
            data, idx, w).compile().as_text()

    flat = compiled_text(
        lambda data, i: gather_rows({"image": data}, i,
                                    {"image": sample})["image"],
        (rows, 3072))
    assert f"u8[{rows},3072]" in flat
    assert _moves_over(flat, rows) == []
    four_d = compiled_text(lambda data, i: data[i], (rows, *sample))
    assert _moves_over(four_d, rows) != []


def _flash_attention_fwd_bwd(q, k, v):
    from tpu_dp.models.sdar import flash_block_diffusion_attention

    def loss(q, k, v):
        out = flash_block_diffusion_attention(q, k, v, 4)
        return jnp.sum(out.astype(jnp.float32))
    return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)


def _expert_products_fwd_bwd(h, gate, up, down):
    from tpu_dp.models.sdar import experts_share, route

    def loss(h, gate, up, down):
        weights, experts = route(h, jnp.ones((h.shape[1], 128), h.dtype), 8)
        out, _ = experts_share(h, weights, experts, gate, up, down, 0, 8)
        return jnp.sum(out)
    return jax.grad(loss, argnums=(0, 1, 2, 3))(h, gate, up, down)


def test_block_diffusion_attention_kernel_compiles_for_v5e(one_chip,
                                                           compiled_kernels):
    """The flash kernel pair (`tpu_dp/ops/flash_block_diffusion.py`: the
    block-diffusion mask computed from the indices inside it, the whole-row
    accumulators of ``dk`` and ``dv`` in fast memory), forward and
    backward, at the published widths (32 heads over 4 key/value heads of
    128) on one row of 2 x 4,096 positions: two Mosaic kernels by the
    names the benchmark reads, and temporaries under half a GiB (sixteen
    partial ``dq`` of a row, one a key tile, would be a whole one). The
    score array of a row would be 8.6 GB in float32; the whole call stays
    under 2 GiB."""
    n2 = 2 * 4096
    compiled = _compile(
        _flash_attention_fwd_bwd, one_chip,
        ((1, n2, 32, 128), jnp.bfloat16), ((1, n2, 4, 128), jnp.bfloat16),
        ((1, n2, 4, 128), jnp.bfloat16))
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes \
        + mem.output_size_in_bytes < 2 * 2**30
    assert mem.temp_size_in_bytes < 2**29
    kernels = re.findall(r' custom-call\(.*op_name="([^"]*)/pallas_call"',
                         compiled.as_text())
    assert len(kernels) == 2 and all("splash_mqa" in k for k in kernels)


def test_grouped_expert_products_are_a_kernel_of_the_compilers(
        one_chip, compiled_kernels):
    """`lax.ragged_dot` over the held experts is a tiled grouped product of
    the TPU compiler's own (a ``tpu_custom_call``), forward and backward,
    and not sixteen dense products masked afterwards: 8,192 positions, 16
    experts of 2048 x 768, as a chunk of the cell's layer has them."""
    _compile(
        _expert_products_fwd_bwd, one_chip,
        ((8192, 2048), jnp.bfloat16), ((16, 2048, 768), jnp.float32),
        ((16, 2048, 768), jnp.float32), ((16, 768, 2048), jnp.float32))


def _qk_norm_rope_fwd_bwd(x, w, cos, sin, dy):
    from tpu_dp.ops.qk_norm_rope import qk_norm_rope

    def loss(x, w):
        y = qk_norm_rope(x, w, cos, sin, 1e-6, 128 ** -0.5)
        return jnp.sum(y.astype(jnp.float32) * dy)
    return jax.value_and_grad(loss, argnums=(0, 1))(x, w)


@pytest.mark.parametrize("shape", [(4, 8192, 32, 128), (4, 8192, 4, 128)],
                         ids=["q", "k"])
def test_qk_norm_rope_kernels_compile_for_v5e(one_chip, compiled_kernels,
                                              shape):
    """The fused RMSNorm-and-RoPE of q and k, forward and backward, at the
    token cell's shapes (4 rows of 8,192 positions, 32 query and 4
    key/value heads of 128 lanes, bfloat16): two Mosaic kernels whose
    blocks fit a kernel's fast memory, and no float32 copy of the operand
    among the temporaries (the result and one more array of its size are
    all there is)."""
    compiled = _compile(
        _qk_norm_rope_fwd_bwd, one_chip, (shape, jnp.bfloat16),
        ((128,), jnp.float32), ((8192, 128), jnp.float32),
        ((8192, 128), jnp.float32),
        ((shape[0], shape[2], shape[1], shape[3]), jnp.bfloat16))
    text = compiled.as_text()
    kernels = re.findall(r' custom-call\(.*op_name="([^"]*)/pallas_call"', text)
    assert len(kernels) == 2 and all("qk_norm_rope" in k for k in kernels)
    operand = 2 * math.prod(shape)
    assert compiled.memory_analysis().temp_size_in_bytes < 2.1 * operand


def _causal_attention_fwd_bwd(q, k, v):
    from tpu_dp.ops.flash_block_diffusion import CausalMask, flash_attention

    def loss(q, k, v):
        out = flash_attention(q, k, v, CausalMask(q.shape[-2]))
        return jnp.sum(out.astype(jnp.float32))
    return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)


def test_causal_attention_kernel_compiles_for_v5e(one_chip, compiled_kernels):
    """The same flash pair under its causal mask, forward and backward, at
    the hybrid cell's widths (32 heads over 2 key/value heads of 128, so 16
    query heads a key/value head, eight a grid step) on two rows of 8,192
    positions: two Mosaic kernels by the names `causal_attention_roofline`
    reads, and not the block-diffusion pair's; temporaries under half a
    GiB where the full scores would be 17 GB."""
    compiled = _compile(
        _causal_attention_fwd_bwd, one_chip,
        ((2, 2, 16, 8192, 128), jnp.bfloat16),
        ((2, 2, 8192, 128), jnp.bfloat16), ((2, 2, 8192, 128), jnp.bfloat16))
    assert compiled.memory_analysis().temp_size_in_bytes < 2**29
    kernels = re.findall(r' custom-call\(.*op_name="([^"]*)/pallas_call"',
                         compiled.as_text())
    assert len(kernels) == 2
    assert all("splash_mqa_causal" in k for k in kernels)


def _ssd_scan_fwd_bwd(x, delta, a, b, c, d):
    from tpu_dp.ops.ssd_scan import ssd_scan

    def loss(*operands):
        return jnp.sum(ssd_scan(*operands, 8))
    return jax.grad(loss, argnums=tuple(range(6)))(x, delta, a, b, c, d)


def test_ssd_scan_kernels_compile_for_v5e(one_chip, compiled_kernels):
    """The state-space scan's pair, forward and backward, at the hybrid
    cell's widths (64 heads of 64 in 8 groups, state 128) on a row of 8,192
    positions: two Mosaic kernels under the one name `ssd_scan_roofline`
    reads; the temporaries are the entering states (128 MiB) and no array
    of ``[tokens, heads, 128]`` float32 (512 MiB each)."""
    from tpu_dp.ops import ssd_scan

    assert ssd_scan.fits(8192, 128, 64, 64, 8, 128)
    compiled = _compile(
        _ssd_scan_fwd_bwd, one_chip,
        ((1, 8192, 4096), jnp.bfloat16), ((1, 8192, 64), jnp.float32),
        ((64,), jnp.float32), ((1, 8192, 1024), jnp.bfloat16),
        ((1, 8192, 1024), jnp.bfloat16), ((64,), jnp.float32))
    assert compiled.memory_analysis().temp_size_in_bytes < 2**29
    kernels = re.findall(r' custom-call\(.*op_name="([^"]*)/pallas_call"',
                         compiled.as_text())
    assert len(kernels) == 2
    assert all(ssd_scan.NAME in k for k in kernels), kernels


def test_the_hybrid_step_compiles_for_v5e_and_fits_the_chip(one_chip,
                                                            compiled_kernels):
    """The whole train step of `nemotron_h` at the published widths and the
    cell's shapes (preset `nemotron3_nano_30b_a3b_ep16`: 666,963,456
    parameters, 2 rows of 8,192 tokens, bfloat16 compute, AdamW with the
    clip), built by the step factory every model trains through, compiled
    for one described v5e chip: the causal pair and the scan's pair are in
    it, and the state,
    the gradient and every temporary together fit the chip's 16 GiB with a
    GiB to spare for what the runtime holds beside a program (the resident
    rows, the loop's metrics)."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from tpu_dp.config import parse_cli
    from tpu_dp.models import DECODER_SHAPES, build_model
    from tpu_dp.train import constant_lr, make_train_step
    from tpu_dp.train.optim import AdamW
    from tpu_dp.train.state import TrainState

    cfg = parse_cli(["--preset=nemotron3_nano_30b_a3b_ep16"])
    model = build_model(
        cfg.model.name, num_classes=cfg.model.num_classes, dtype=jnp.bfloat16,
        **{k: getattr(cfg.model, k) for k in DECODER_SHAPES})
    opt = AdamW(cfg.optim.b1, cfg.optim.b2, cfg.optim.eps,
                cfg.optim.weight_decay, cfg.optim.clip_norm,
                cfg.optim.decay_exclude_bias_and_norm)
    mesh = Mesh(np.array(list(one_chip.device_set)), ("data",))
    whole = NamedSharding(mesh, PartitionSpec())

    def described(tree):
        return jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=whole),
            tree)

    shapes = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0))["params"])
    assert sum(s.size for s in jax.tree_util.tree_leaves(shapes)) \
        == 666_963_456
    state = TrainState(
        step=jax.ShapeDtypeStruct((), jnp.int32, sharding=whole),
        params=described(shapes),
        opt_state=described(jax.eval_shape(opt.init, shapes)), batch_stats={})
    batch = {"tokens": jax.ShapeDtypeStruct(
        (cfg.data.batch_size, cfg.data.seq_len), jnp.int32,
        sharding=NamedSharding(mesh, PartitionSpec("data")))}
    step = make_train_step(model, opt, mesh, constant_lr(cfg.optim.lr))
    compiled = step.lower(state, batch).compile()
    text = compiled.as_text()
    assert text.count("/splash_mqa_causal_pair/pallas_call") >= 2
    assert "splash_mqa_block_diffusion" not in text
    # four state-space layers: the scan's forward, its recomputation and
    # its backward are kernels, and no cumulative sum is the compiler's
    scans = re.findall(r' custom-call\(.*/ssd_scan_pair/pallas_call"', text)
    assert len(scans) == 12
    assert not re.search(r'reduce-window\(.*tpu_dp\.ssm_scan', text)
    mem = compiled.memory_analysis()
    held = (mem.temp_size_in_bytes + mem.argument_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    assert held < 15 * 2**30, held / 2**30
    # parameters and the two moments are arguments, updated in place
    assert mem.argument_size_in_bytes > 12 * 666_963_456
