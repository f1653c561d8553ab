"""ResNet family — the reference's headline benchmark workload.

The reference's benchmark story is ResNet-50/101 ImageNet throughput and
scaling (README.md:45-51, docs/benchmarks.md:22-40,
examples/keras_imagenet_resnet50.py); this module provides the TPU-native
model.  TPU-first choices:

* NHWC layout, bfloat16 activations, float32 parameters and batch-norm
  statistics — keeps conv GEMMs on the MXU at full rate.
* ResNet-v1.5 (stride-2 in the 3×3, as the reference's Keras ResNet50
  weights use) with channel counts already multiples of 128.
* No data-dependent control flow — a single static graph XLA can fuse.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, Sequence, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

ModuleDef = Any


class BottleneckBlock(nn.Module):
    filters: int
    strides: Tuple[int, int]
    conv: ModuleDef
    norm: ModuleDef

    @nn.compact
    def __call__(self, x):
        residual = x
        y = self.conv(self.filters, (1, 1))(x)
        y = nn.relu(self.norm()(y))
        y = self.conv(self.filters, (3, 3), self.strides)(y)
        y = nn.relu(self.norm()(y))
        y = self.conv(self.filters * 4, (1, 1))(y)
        # Zero-init the last BN scale: standard large-batch recipe from the
        # same Goyal et al. playbook the reference's LR-warmup callback
        # implements (keras/callbacks.py:202-259).
        y = self.norm(scale_init=nn.initializers.zeros)(y)
        if residual.shape != y.shape:
            residual = self.conv(self.filters * 4, (1, 1),
                                 self.strides, name="conv_proj")(residual)
            residual = self.norm(name="norm_proj")(residual)
        return nn.relu(residual + y)


class ResNet(nn.Module):
    stage_sizes: Sequence[int]
    num_classes: int = 1000
    num_filters: int = 64
    compute_dtype: Any = jnp.bfloat16
    # MLPerf space-to-depth stem: a 3-input-channel 7x7 conv cannot fill
    # the 128-lane MXU; rearranging 2x2 pixel blocks into 12 channels and
    # convolving 4x4/s1 computes the same stage (equivalent to a
    # zero-padded 8x8/s2 conv, a superset of the 7x7) with 4x the MXU
    # input-channel occupancy.
    space_to_depth: bool = False

    @nn.compact
    def __call__(self, x, train: bool = True):
        conv = partial(nn.Conv, use_bias=False, dtype=self.compute_dtype,
                       padding="SAME")
        norm = partial(nn.BatchNorm, use_running_average=not train,
                       momentum=0.9, epsilon=1e-5, dtype=self.compute_dtype,
                       param_dtype=jnp.float32, axis_name=None)
        x = x.astype(self.compute_dtype)
        if self.space_to_depth:
            b, h, w, c = x.shape
            if h % 2 or w % 2:
                # Pad odd extents so the 2x2 block rearrange is defined
                # (SAME-conv tolerance, matching the 7x7/s2 stem).
                x = jnp.pad(x, ((0, 0), (0, h % 2), (0, w % 2), (0, 0)))
                b, h, w, c = x.shape
            x = x.reshape(b, h // 2, 2, w // 2, 2, c)
            x = x.transpose(0, 1, 3, 2, 4, 5).reshape(
                b, h // 2, w // 2, 4 * c)
            x = conv(self.num_filters, (4, 4), (1, 1),
                     name="conv_init")(x)
        else:
            x = conv(self.num_filters, (7, 7), (2, 2),
                     name="conv_init")(x)
        x = nn.relu(norm(name="bn_init")(x))
        x = nn.max_pool(x, (3, 3), strides=(2, 2), padding="SAME")
        for i, block_count in enumerate(self.stage_sizes):
            for j in range(block_count):
                strides = (2, 2) if i > 0 and j == 0 else (1, 1)
                x = BottleneckBlock(self.num_filters * 2 ** i, strides,
                                    conv=conv, norm=norm)(x)
        x = jnp.mean(x, axis=(1, 2))
        x = nn.Dense(self.num_classes, dtype=self.compute_dtype,
                     name="head")(x)
        return x.astype(jnp.float32)


ResNet50 = partial(ResNet, stage_sizes=[3, 4, 6, 3])
ResNet101 = partial(ResNet, stage_sizes=[3, 4, 23, 3])
ResNet18Thin = partial(ResNet, stage_sizes=[1, 1, 1, 1], num_filters=16)


def init_resnet(model: nn.Module, image_size: int = 224,
                batch_size: int = 8, seed: int = 0):
    """Initialize params + batch_stats."""
    rng = jax.random.PRNGKey(seed)
    dummy = jnp.zeros((batch_size, image_size, image_size, 3), jnp.float32)
    # One compiled program: un-jitted, flax's init dispatches (and
    # compiles) every initializer and layer op by op.
    variables = jax.jit(partial(model.init, train=False))(rng, dummy)
    return variables["params"], variables.get("batch_stats", {})


def resnet_loss_fn(model: nn.Module, weight_decay: float = 1e-4):
    """Softmax CE + L2, returning (loss, new_batch_stats) for mutable BN.

    Matches the reference ResNet-50 example's objective
    (examples/keras_imagenet_resnet50.py:118-124: categorical CE + the
    weight decay baked into its conv kernels)."""

    def loss_fn(params, batch_stats, batch):
        images, labels = batch
        logits, mutated = model.apply(
            {"params": params, "batch_stats": batch_stats}, images,
            train=True, mutable=["batch_stats"])
        logp = jax.nn.log_softmax(logits)
        onehot = jax.nn.one_hot(labels, logits.shape[-1])
        ce = -jnp.mean(jnp.sum(onehot * logp, axis=-1))
        l2 = sum(jnp.sum(p.astype(jnp.float32) ** 2)
                 for p in jax.tree_util.tree_leaves(params)
                 if p.ndim > 1)
        return ce + weight_decay * 0.5 * l2, mutated["batch_stats"]

    return loss_fn


def synthetic_imagenet(num: int, image_size: int = 224, seed: int = 0,
                       num_classes: int = 1000):
    """Synthetic ImageNet-shaped batch (the reference benchmarks use
    synthetic data too — docs/benchmarks.md:28-33 '--data_name imagenet'
    with no data dir)."""
    import numpy as np

    rng = np.random.RandomState(seed)
    images = rng.rand(num, image_size, image_size, 3).astype("float32")
    labels = rng.randint(0, num_classes, size=(num,)).astype("int32")
    return images, labels
