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
        out = flash_block_diffusion_attention(q, k, v, 4,
                                              _partition.interpret())
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
    """The shipped flash kernel with the block-diffusion mask computed from
    the indices inside it, forward and backward, at the published widths
    (32 heads over 4 key/value heads of 128) on one row of 2 x 4,096
    positions. The score array of a row would be 8.6 GB in float32; the
    whole call stays under 2 GiB."""
    n2 = 2 * 4096
    compiled = _compile(
        _flash_attention_fwd_bwd, one_chip,
        ((1, n2, 32, 128), jnp.bfloat16), ((1, n2, 4, 128), jnp.bfloat16),
        ((1, n2, 4, 128), jnp.bfloat16))
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes \
        + mem.output_size_in_bytes < 2 * 2**30


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
