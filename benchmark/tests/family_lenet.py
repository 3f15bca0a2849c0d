"""A family the harness has never named: the program's LeNet (`model.name=net`)
trained with plain SGD and momentum, for `test_family_seam.py`. Another
parameter tree (five layers with biases, no norms), its own plain reference
in `jax.numpy`, its own work count; only the image generator is shared.
"""

import jax
import jax.numpy as jnp
import numpy as np

import datagen
from compare import leaf_norms

# (name, kernel, cin, cout, output height = width); dense layers are 1x1.
LAYERS = (("conv1", 5, 3, 6, 28), ("conv2", 5, 6, 16, 10),
          ("fc1", 1, 400, 120, 1), ("fc2", 1, 120, 84, 1),
          ("fc3", 1, 84, 10, 1))
HIGHEST = jax.lax.Precision.HIGHEST


def items_per_row(job) -> int:
    return 1


def datasets(job):
    from tpu_dp.data.cifar import ArrayDataset

    images, labels = datagen.make_dataset(job.seed, job.train_size, 10)
    return (ArrayDataset(images, labels, "lenet", 10, synthetic=True),
            ArrayDataset(images[:job.global_batch],
                         labels[:job.global_batch], "lenet", 10,
                         synthetic=True))


def init_params(job):
    """N(0, 1/fan_in) kernels and zero biases, keyed by the layer's place."""
    root, params = jax.random.PRNGKey(job.seed), {}
    for i, (name, k, cin, cout, _) in enumerate(LAYERS):
        shape = (k, k, cin, cout) if name.startswith("conv") else (cin, cout)
        kernel = jax.random.normal(jax.random.fold_in(root, i), shape,
                                   jnp.float32) / np.sqrt(k * k * cin)
        params[name] = {"kernel": kernel, "bias": jnp.zeros((cout,))}
    return params


def first_gradient(opt_state, params0, optimizer: dict):
    """No weight decay: after step 1 the momentum buffer is the gradient."""
    return opt_state


def variants(job) -> dict:
    return {"precisions": ("bfloat16",), "faults": ("half_batch",)}


def _forward(params, x, dtype):
    def conv_pool(x, p):
        y = jax.lax.conv_general_dilated(
            x.astype(dtype), p["kernel"].astype(dtype), (1, 1), "VALID",
            dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HIGHEST)
        y = jax.nn.relu(y.astype(jnp.float32) + p["bias"])
        n, h, w, c = y.shape
        return y.reshape(n, h // 2, 2, w // 2, 2, c).max(axis=(2, 4))

    x = conv_pool(conv_pool(x, params["conv1"]), params["conv2"])
    x = x.reshape(x.shape[0], -1)
    for name in ("fc1", "fc2", "fc3"):
        x = jnp.dot(x.astype(dtype), params[name]["kernel"].astype(dtype),
                    precision=HIGHEST).astype(jnp.float32)
        x = x + params[name]["bias"]
        x = jax.nn.relu(x) if name != "fc3" else x
    return x


def _loss(params, x, y, dtype):
    logits = _forward(params, x, dtype)
    true = jnp.take_along_axis(logits, y[:, None], axis=-1)[:, 0]
    return jnp.mean(jax.nn.logsumexp(logits, axis=-1) - true)


def reference_readings(job, steps: int, precision: str = "float32",
                       fault: str | None = None) -> dict:
    opt = job.config["optimizer"]
    dtype = jnp.bfloat16 if precision == "bfloat16" else jnp.float32
    images, labels = datagen.device_dataset(job.seed, job.train_size, 10)
    params0 = init_params(job)
    params = params0
    buf = jax.tree_util.tree_map(jnp.zeros_like, params0)
    losses, grad1 = [], None
    for k in range(steps):
        rows = datagen.step_rows(job.program_seed, 0, job.train_size,
                                 job.global_batch, k)
        if fault == "half_batch":
            rows = rows[:len(rows) // 2]
        x = images[rows].astype(jnp.float32) * (2.0 / 255.0) - 1.0
        loss, grads = jax.value_and_grad(_loss)(params, x, labels[rows], dtype)
        buf = jax.tree_util.tree_map(
            lambda b, g: opt["momentum"] * b + g, buf, grads)
        params = jax.tree_util.tree_map(
            lambda p, b: p - opt["lr"] * b, params, buf)
        losses.append(float(loss))
        if k == 0:
            grad1 = leaf_norms(grads)
    delta = leaf_norms(jax.tree_util.tree_map(
        lambda a, b: a - b, params, params0))
    host = lambda d: {k: float(v) for k, v in d.items()}  # noqa: E731
    return {"loss": losses, "grad1": host(grad1), "delta": host(delta)}


def _macs() -> list[int]:
    return [hw * hw * k * k * cin * cout for _, k, cin, cout, hw in LAYERS]


def train_flops_per_item(job) -> float:
    """Forward 2 x MACs, backward twice that, no input gradient for conv1."""
    macs = _macs()
    return 6.0 * sum(macs) - 2.0 * macs[0]


def least_step_seconds(job, peaks: dict) -> dict:
    """The batch's FLOPs over the peak: at these sizes no pass is bound by
    the bandwidth on paper, so this is the whole lower bound."""
    seconds = (train_flops_per_item(job) * job.batch_per_chip
               / peaks["bf16_flops_per_s"])
    return {"seconds": seconds, "bandwidth_bound_seconds": 0.0}
