"""Adversarial dplint fixture — DP301: sharded-update schedule over the
wrong axis — the gradient reduce-scatter and the params all-gather disagree.

The sharded weight update (`train.update_sharding=sharded`, docs/PERF.md)
is only correct when its two ring halves run over the *same* axis: each
replica updates the shard the reduce-scatter handed it, and the all-gather
reassembles exactly those shards. This program scatters over one axis but
gathers over another (the classic wrong-`axis_name` slip once a second mesh
axis exists): every replica updates one shard and gathers a *different*
one — numerically wrong parameters on every replica, while source and
jaxpr both look like a perfectly reasonable scatter/update/gather sequence.
Only the compiled artifact shows the two collectives' replica groups
disagreeing, which is exactly what DP301's sharded-mode classification
checks (`update_sharding: "sharded"` in the hook declaration).
"""

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P



def DPLINT_HLO_PROGRAM():
    # A 2-D mesh: the data axis plus a second (model) axis — the setting
    # where a wrong axis_name literal can even exist.
    devices = np.array(jax.devices()[:8]).reshape(4, 2)
    mesh = Mesh(devices, ("data", "model"))

    def step(g):  # EXPECT: DP301
        flat = jnp.pad(g.reshape(-1), (0, (-g.size) % 2))
        # BUG: the gradient reduce-scatter runs over the *model* axis...
        shard = jax.lax.psum_scatter(  # dplint: allow(DP103) adversarial fixture
            flat, "model", scatter_dimension=0, tiled=True
        ) / 2.0
        new_shard = shard - 0.1 * shard  # the "optimizer update" on 1/N
        # ...but the updated params are all-gathered over *data*: each
        # replica gathers shards it never updated.
        full = jax.lax.all_gather(  # dplint: allow(DP103) adversarial fixture
            new_shard, "data", axis=0, tiled=True
        )
        return full[: g.size].reshape(g.shape)

    # Replication checking off: the typed trace would already refuse a
    # result that still varies over `model`; this fixture is about what
    # the compiled artifact shows.
    fn = jax.jit(jax.shard_map(step, mesh=mesh, in_specs=(P(),),
                               out_specs=P(), check_vma=False))
    return {
        "fn": fn,
        "args": (jnp.zeros((30,), jnp.float32),),
        "update_sharding": "sharded",
        "expect_grad_reduce": True,
    }
