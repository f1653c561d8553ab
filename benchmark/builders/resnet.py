"""The ResNet data-parallel training job, built through the program's
normal entry points: ``hvd.init`` -> ``broadcast_parameters`` ->
``make_train_step_with_state`` -> ``shard_batch``, the overlap schedule
left to the program (``auto``: monolithic on one chip, stream on several).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.builders.base import TrainProgram, check_tree


class _SgdProgram(TrainProgram):
    def first_gradient(self):
        # optax.sgd(momentum) keeps trace_1 = g_1 + momentum * 0.
        for part in self.state[-1]:
            if hasattr(part, "trace"):
                return part.trace
        raise RuntimeError("no momentum trace in the optimizer state")


def build_train(config: dict, job: dict, seed: int, chips: int, ref):
    import optax
    from flax.core import unfreeze

    import horovod_tpu as hvd
    from horovod_tpu.models import resnet as R
    from horovod_tpu.parallel import overlap
    from horovod_tpu.parallel.training import (make_train_step_with_state,
                                               shard_batch)

    m = config["model"]
    n = hvd.size()
    if n != chips:
        raise RuntimeError(f"hvd.size() is {n}, the cell asks for {chips}")
    model = R.ResNet(stage_sizes=list(m["stage_sizes"]),
                     num_classes=m["num_classes"],
                     num_filters=m["num_filters"],
                     compute_dtype=jnp.dtype(m["compute_dtype"]),
                     space_to_depth=m["space_to_depth"])
    per_chip = job["per_chip_batch"]
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.zeros((per_chip, m["image_size"],
                                      m["image_size"], 3), jnp.float32),
                           train=False))
    params = ref.init_params(m, seed)
    check_tree(params, unfreeze(shapes["params"]), "ResNet parameters")
    # Running statistics start at mean 0, variance 1, as flax starts them.
    stats = jax.jit(lambda: jax.tree_util.tree_map_with_path(
        lambda path, s: (jnp.zeros if path[-1].key == "mean" else jnp.ones)(
            s.shape, s.dtype), unfreeze(shapes["batch_stats"])))()
    params = hvd.broadcast_parameters(params, root_rank=0)
    o = job["optimizer"]
    opt = optax.sgd(o["learning_rate"], momentum=o["momentum"])
    step = make_train_step_with_state(
        R.resnet_loss_fn(model, weight_decay=o["l2"]), opt)
    schedule = overlap.resolve_mode(None, hvd.mesh())
    batch = shard_batch(ref.make_batch(m, job, seed, per_chip * n))
    return _SgdProgram(step, [params, stats, opt.init(params)], batch,
                       per_chip * n, {"schedule": schedule, "chips": n})
