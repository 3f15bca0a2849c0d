"""Plain reference of the training step: `jax.numpy`, float32, no program code.

Residual networks as He et al. (arXiv:1512.03385) state them with the CIFAR
stem: convolution, batch norm on the batch's own statistics (biased
variance as E[x^2] - E[x]^2 in float32), ReLU, identity or 1x1-projection
shortcuts, global average pooling, a dense head, mean softmax cross-entropy,
and SGD with momentum and L2 weight decay in torch's form
(``g += wd * p; buf = m * buf + g; p -= lr * buf``) under a linear warm-up
and cosine schedule. Everything the step needs is restated here from the
configuration's file: the initial weights (the initializers the
configuration names, drawn from keys derived as the flax library derives
them from ``PRNGKey(seed)`` and the parameter's path), the pad-4 random
crop and flip keyed by the program's seed plus one and the step, and the
schedule.

``precision`` selects how the contractions are computed:

- ``float32``: operands and results float32, ``Precision.HIGHEST`` — the
  reference proper;
- ``bfloat16``: operands rounded to bfloat16, activations kept in bfloat16,
  as the configurations state their compute type — a second witness;
- ``float8``: everything the configuration computes in bfloat16 (the
  contractions' operands, the activations between layers, and with them
  their cotangents in the backward pass) rounded to float8 (e4m3, unscaled)
  — the control, the nearest precision below the stated one;
- ``float8_operands``: only the contractions' operands rounded to float8,
  activations and cotangents left in bfloat16 — what a scaled float8 matmul
  path would do; read for `PERF.md`, too close to bfloat16 to be told apart.

``fault`` plants one of the faults a cell can have into the reference put
in the program's place: ``half_batch`` (the second half of the rows left
out, the mean taken over the rest) and ``no_exchange`` (one chip's rows
only, as a chip sees them when the exchange is left out).

Each residual block is rematerialised in the backward pass
(`jax.checkpoint`), so that float32 activations of the timed batch fit
beside nothing else on the chip.
"""

from __future__ import annotations

import hashlib

import jax
import jax.numpy as jnp
import numpy as np

from compare import leaf_norms

PRECISIONS = ("float32", "bfloat16", "float8", "float8_operands")


# ---------------------------------------------------------------- parameters

def _path_key(seed_key, path: tuple[str, ...], counter: int = 1):
    """Key of one parameter: sha1 of the module path and the draw's count,
    first four bytes folded into the seed's key (flax.core.scope)."""
    m = hashlib.sha1()
    for name in path:
        m.update(name.encode("utf-8"))
    m.update(counter.to_bytes((counter.bit_length() + 7) // 8, "big"))
    return jax.random.fold_in(
        seed_key, jnp.uint32(int.from_bytes(m.digest()[:4], "big")))


def _conv_init(key, k: int, cin: int, cout: int):
    """He normal on fan-out: N(0, 2 / (k*k*cout))."""
    std = jnp.sqrt(jnp.float32(2.0 / (k * k * cout)))
    return jax.random.normal(key, (k, k, cin, cout), jnp.float32) * std


def _dense_init(key, cin: int, cout: int):
    """LeCun normal: N(0, 1/cin) truncated at two standard deviations."""
    std = jnp.sqrt(jnp.float32(1.0 / cin)) / jnp.float32(0.87962566103423978)
    return jax.random.truncated_normal(
        key, -2.0, 2.0, (cin, cout), jnp.float32) * std


def _bn_init(c: int, zero_scale: bool = False):
    scale = jnp.zeros if zero_scale else jnp.ones
    return {"scale": scale((c,), jnp.float32),
            "bias": jnp.zeros((c,), jnp.float32)}


def _block_plan(model: dict):
    """(name, stride, filters, cin) of every block, in order."""
    kind = "BasicBlock" if model["block"] == "basic" else "BottleneckBlock"
    cin = int(model["stem_filters"])
    n = 0
    for stage, (filters, count) in enumerate(
            zip(model["stage_filters"], model["stage_blocks"])):
        for j in range(int(count)):
            stride = 2 if stage > 0 and j == 0 else 1
            yield f"{kind}_{n}", stride, int(filters), cin
            cin = int(filters) * (1 if model["block"] == "basic" else 4)
            n += 1


def init_params(model: dict, seed: int) -> dict:
    """Initial parameters, as a tree of the module names the paths hash.
    One jitted call; the seed enters as the key, so every seed shares it."""
    return jax.jit(lambda root: _init_from_key(model, root))(
        jax.random.PRNGKey(int(seed)))


def _init_from_key(model: dict, root) -> dict:
    basic = model["block"] == "basic"
    stem = int(model["stem_filters"])
    params = {
        "stem_conv": {"kernel": _conv_init(
            _path_key(root, ("stem_conv",)), 3,
            int(model["image_channels"]), stem)},
        "stem_norm": _bn_init(stem),
    }
    cin = stem
    for name, stride, filters, cin in _block_plan(model):
        cout = filters if basic else 4 * filters
        shapes = ([(3, cin, filters), (3, filters, filters)] if basic else
                  [(1, cin, filters), (3, filters, filters),
                   (1, filters, cout)])
        blk = {}
        for i, (k, ci, co) in enumerate(shapes):
            blk[f"Conv_{i}"] = {"kernel": _conv_init(
                _path_key(root, (name, f"Conv_{i}")), k, ci, co)}
            # The last norm of a block starts at zero, so that every block
            # starts as the identity (the configuration's recipe).
            blk[f"BatchNorm_{i}"] = _bn_init(
                co, zero_scale=(i == len(shapes) - 1))
        if stride != 1 or cin != cout:
            blk["shortcut_conv"] = {"kernel": _conv_init(
                _path_key(root, (name, "shortcut_conv")), 1, cin, cout)}
            blk["shortcut_norm"] = _bn_init(cout)
        params[name] = blk
        cin = cout
    params["classifier"] = {
        "kernel": _dense_init(_path_key(root, ("classifier",)), cin,
                              int(model["num_classes"])),
        "bias": jnp.zeros((int(model["num_classes"]),), jnp.float32),
    }
    return params


# ------------------------------------------------------------------- forward

@jax.custom_vjp
def _store8(x):
    """Round to float8 (e4m3) in place, and the cotangent with it."""
    return x.astype(jnp.float8_e4m3fn).astype(x.dtype)


def _store8_fwd(x):
    return _store8(x), None


def _store8_bwd(_, g):
    return (g.astype(jnp.float8_e4m3fn).astype(g.dtype),)


_store8.defvjp(_store8_fwd, _store8_bwd)


def _round_operand(x, precision: str):
    if precision == "float32":
        return x
    if precision == "float8_operands":
        x = x.astype(jnp.float8_e4m3fn)
    x = x.astype(jnp.bfloat16)
    return _store8(x) if precision == "float8" else x


def _conv(x, w, stride: int, precision: str):
    xo, wo = _round_operand(x, precision), _round_operand(w, precision)
    return jax.lax.conv_general_dilated(
        xo, wo, (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=(jax.lax.Precision.HIGHEST if precision == "float32"
                   else None),
    )


def _batch_norm(x, p, eps: float, act_dtype, store=lambda a: a):
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=(0, 1, 2))
    mean2 = jnp.mean(jnp.square(xf), axis=(0, 1, 2))
    var = jnp.maximum(mean2 - jnp.square(mean), 0.0)
    mul = jax.lax.rsqrt(var + eps) * p["scale"]
    return store(((xf - mean) * mul + p["bias"]).astype(act_dtype))


def _block(p, x, stride: int, basic: bool, eps: float, precision: str,
           act_dtype):
    store = _store8 if precision == "float8" else (lambda a: a)
    convs = sorted(k for k in p if k.startswith("Conv_"))
    strides = ([stride, 1] if basic else [1, stride, 1])
    y = x
    for i, name in enumerate(convs):
        y = _conv(y, p[name]["kernel"], strides[i], precision)
        y = _batch_norm(y, p[f"BatchNorm_{i}"], eps, act_dtype, store)
        if i < len(convs) - 1:
            y = jax.nn.relu(y)
    if "shortcut_conv" in p:
        x = _conv(x, p["shortcut_conv"]["kernel"], stride, precision)
        x = _batch_norm(x, p["shortcut_norm"], eps, act_dtype, store)
    return store(jax.nn.relu(y + x))


def forward(params: dict, images, model: dict, precision: str = "float32"):
    """Logits (float32) of normalised NHWC ``images`` in training mode."""
    if precision not in PRECISIONS:
        raise ValueError(f"precision must be one of {PRECISIONS}")
    act = jnp.float32 if precision == "float32" else jnp.bfloat16
    eps = float(model["bn_epsilon"])
    basic = model["block"] == "basic"
    x = images.astype(act)
    store = _store8 if precision == "float8" else (lambda a: a)
    x = _conv(x, params["stem_conv"]["kernel"], 1, precision)
    x = jax.nn.relu(_batch_norm(x, params["stem_norm"], eps, act, store))
    for name, stride, _, _ in _block_plan(model):
        blk = jax.checkpoint(
            lambda p, h, s=stride: _block(p, h, s, basic, eps, precision, act))
        x = blk(params[name], x)
    x = jnp.mean(x, axis=(1, 2)).astype(jnp.float32)
    head = params["classifier"]
    return jnp.dot(x, head["kernel"],
                   precision=jax.lax.Precision.HIGHEST) + head["bias"]


def loss_fn(params, images, labels, model, precision):
    logits = forward(params, images, model, precision)
    logz = jax.nn.logsumexp(logits, axis=-1)
    true = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.mean(logz - true)


# ------------------------------------------------------- inputs and schedule

def normalize(images_u8):
    """uint8 [0, 255] to float32 [-1, 1]: ToTensor, then Normalize(.5, .5)."""
    return images_u8.astype(jnp.float32) * (2.0 / 255.0) - 1.0


def augment(aug_key, step, images, pad: int = 4, fill: float = -1.0):
    """Pad-``pad`` random crop and horizontal flip of each image, keyed by
    ``aug_key`` (``PRNGKey(seed + 1)``) and the global step."""
    n, h, w, c = images.shape
    key = jax.random.fold_in(aug_key, step)
    k_off, k_flip = jax.random.split(key)
    padded = jnp.pad(images, ((0, 0), (pad, pad), (pad, pad), (0, 0)),
                     constant_values=fill)
    offsets = jax.random.randint(k_off, (n, 2), 0, 2 * pad + 1)
    flips = jax.random.bernoulli(k_flip, 0.5, (n,))

    def one(img, off, flip):
        crop = jax.lax.dynamic_slice(img, (off[0], off[1], 0), (h, w, c))
        return jnp.where(flip, crop[:, ::-1, :], crop)

    return jax.vmap(one)(padded, offsets, flips)


def learning_rate(opt: dict, step, steps_per_epoch: int):
    """Linear warm-up from 0 over ``warmup_epochs``, then cosine."""
    base, final = float(opt["lr"]), float(opt["final_lr"])
    if opt["schedule"] == "constant":
        return jnp.asarray(base, jnp.float32)
    warm = int(float(opt["warmup_epochs"]) * steps_per_epoch)
    total = int(opt["epochs"]) * steps_per_epoch
    step = jnp.asarray(step, jnp.float32)
    ramp = base * step / max(1.0, warm)
    t = jnp.clip((step - warm) / max(1.0, total - warm), 0.0, 1.0)
    cos = final + 0.5 * (base - final) * (1.0 + jnp.cos(jnp.pi * t))
    return jnp.where(step < warm, ramp, cos).astype(jnp.float32)


# ---------------------------------------------------------------------- step

def make_step(model: dict, opt: dict, steps_per_epoch: int,
              augmented: bool, precision: str = "float32",
              fault: str | None = None, chips: int = 1):
    """``step(params, buf, images_u8, labels, k, aug_key) -> (params, buf,
    loss, grad_norms)``: one optimizer step, step counter ``k`` from 0. The
    seed enters as data (the parameters and ``aug_key``), so one compiled
    program serves every seed."""
    if fault not in (None, "half_batch", "no_exchange"):
        raise ValueError(f"unknown fault {fault!r}")
    m, wd = float(opt["momentum"]), float(opt["weight_decay"])

    def step(params, buf, images_u8, labels, k, aug_key):
        x = normalize(images_u8)
        if augmented:
            x = augment(aug_key, k, x)
        if fault is not None:
            keep = x.shape[0] // (2 if fault == "half_batch" else chips)
            x, labels = x[:keep], labels[:keep]
        loss, grads = jax.value_and_grad(loss_fn)(
            params, x, labels, model, precision)
        lr = learning_rate(opt, k, steps_per_epoch)
        g = jax.tree_util.tree_map(lambda g, p: g + wd * p, grads, params)
        buf = jax.tree_util.tree_map(lambda b, g: m * b + g, buf, g)
        params = jax.tree_util.tree_map(lambda p, b: p - lr * b, params, buf)
        return params, buf, loss, leaf_norms(grads)

    return step


def follow(model: dict, opt: dict, seed: int, program_seed: int,
           steps_per_epoch: int, augmented: bool, batches,
           precision: str = "float32",
           fault: str | None = None, chips: int = 1,
           batch_sharding=None) -> dict:
    """Follow the first ``len(batches)`` steps: weights from ``seed``,
    crops from ``program_seed + 1``.

    ``batches`` yields ``(images_u8, labels)`` device arrays, one a step.
    Returns the losses, the first gradient's norm by leaf and the norm of
    the parameters' change by leaf, as host floats.
    """
    params0 = init_params(model, seed)
    buf = jax.tree_util.tree_map(jnp.zeros_like, params0)
    fn = make_step(model, opt, steps_per_epoch, augmented, precision,
                   fault, chips)
    aug_key = jax.random.PRNGKey(int(program_seed) + 1)
    if batch_sharding is not None:
        repl = jax.sharding.NamedSharding(
            batch_sharding.mesh, jax.sharding.PartitionSpec())
        fn = jax.jit(fn, in_shardings=(repl, repl, batch_sharding,
                                       batch_sharding, repl, repl),
                     out_shardings=repl)
        params0 = jax.device_put(params0, repl)
        buf = jax.device_put(buf, repl)
    else:
        fn = jax.jit(fn)
    params, losses, grad1 = params0, [], None
    for k, (images, labels) in enumerate(batches):
        params, buf, loss, gn = fn(params, buf, images, labels,
                                   jnp.int32(k), aug_key)
        losses.append(loss)
        if k == 0:
            grad1 = gn
    delta = leaf_norms(jax.tree_util.tree_map(
        lambda a, b: a - b, params, params0))
    to_host = lambda d: {k: float(np.asarray(v)) for k, v in d.items()}  # noqa: E731
    return {"loss": [float(np.asarray(x)) for x in losses],
            "grad1": to_host(grad1), "delta": to_host(delta)}
