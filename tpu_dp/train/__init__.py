"""Training loop layer: state, optimizer, schedules, compiled steps, Trainer.

TPU-native replacement of the reference's L4 layer
(`/root/reference/cifar_example.py:66-87`, `cifar_example_ddp.py:90-114`):
the eager zero_grad/forward/backward/step loop with DDP hook-based gradient
allreduce becomes ONE compiled XLA program per step — forward, backward,
cross-chip gradient mean, and the SGD update fused and scheduled together.
"""

from tpu_dp.train.optim import SGD, Optimizer, ShardedUpdate, shard_optimizer
from tpu_dp.train.schedule import constant_lr, cosine_lr, make_schedule
from tpu_dp.train.state import TrainState, create_train_state
from tpu_dp.train.step import (
    cross_entropy_loss,
    make_eval_step,
    make_local_step,
    make_train_step,
)
from tpu_dp.train.trainer import Trainer

__all__ = [
    "SGD",
    "Optimizer",
    "ShardedUpdate",
    "Trainer",
    "TrainState",
    "shard_optimizer",
    "constant_lr",
    "cosine_lr",
    "create_train_state",
    "cross_entropy_loss",
    "make_eval_step",
    "make_local_step",
    "make_schedule",
    "make_train_step",
]
