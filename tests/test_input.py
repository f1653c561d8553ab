"""hvd-pipeline input half: double-buffered device prefetch
(parallel/input.py), the batched device_put satellites, the async
train-loop plumbing (barrier_fence, the in-flight window) and the
host-stall telemetry."""

import threading
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

import horovod_tpu
from horovod_tpu.parallel.input import (PrefetchIterator, device_put_batch,
                                        prefetch_to_device)
from horovod_tpu.parallel.training import (barrier_fence, batch_sharding,
                                           make_train_step, shard_batch,
                                           shard_parallel_batch)


def _batches(n, rows=16, cols=4, tag=0):
    for i in range(n):
        rng = np.random.RandomState(100 * tag + i)
        yield {"x": rng.normal(size=(rows, cols)).astype("float32"),
               "i": np.full((rows,), i, dtype="int32")}


# ---------------------------------------------------------------------------
# Prefetch contract
# ---------------------------------------------------------------------------

def test_prefetch_preserves_order_and_values(hvd):
    got = list(prefetch_to_device(_batches(6)))
    assert len(got) == 6
    for i, (ref, dev) in enumerate(zip(_batches(6), got)):
        assert int(dev["i"][0]) == i
        np.testing.assert_array_equal(np.asarray(dev["x"]), ref["x"])
        # Correct per-leaf placement: the data-parallel default sharding.
        assert dev["x"].sharding == batch_sharding()


def test_prefetch_bounded_depth(hvd):
    """The stager never runs more than ``depth`` batches ahead of the
    consumer (plus the one it is currently staging)."""
    produced = []

    def loader():
        for i in range(20):
            produced.append(i)
            yield np.full((8,), i, dtype="float32")

    it = prefetch_to_device(loader(), depth=2)
    time.sleep(0.5)  # let the stager run as far ahead as it ever will
    # depth staged + at most one in the stager's hands.
    assert len(produced) <= 2 + 1, produced
    consumed = 0
    for _ in it:
        consumed += 1
        if consumed == 10:
            time.sleep(0.2)
            assert len(produced) <= consumed + 2 + 1, (len(produced),
                                                       consumed)
    assert consumed == 20
    it.close()


def test_prefetch_depth_validation(hvd):
    with pytest.raises(ValueError, match="depth"):
        prefetch_to_device(_batches(1), depth=0)


def test_prefetch_loader_exception_propagates_with_traceback(hvd):
    """A loader crash re-raises at the consuming step — the ORIGINAL
    exception object, stager-side frames intact — and is flight-recorded."""
    def exploding():
        yield np.zeros((8,), "float32")
        raise ValueError("corrupt shard 7")

    errors_before = horovod_tpu.metrics().get(
        "input.prefetch_errors", {}).get("value", 0)
    it = prefetch_to_device(exploding(), depth=2)
    next(it)
    with pytest.raises(ValueError, match="corrupt shard 7") as exc_info:
        next(it)
    tb = "".join(traceback.format_exception(
        exc_info.type, exc_info.value, exc_info.tb))
    assert "exploding" in tb  # the loader frame survived the thread hop
    # Exhausted after the error: the iterator is dead, not wedged.
    with pytest.raises(StopIteration):
        next(it)
    errors_after = horovod_tpu.metrics()[
        "input.prefetch_errors"]["value"]
    assert errors_after == errors_before + 1


def test_prefetch_clean_shutdown_mid_epoch(hvd):
    """close() with a full queue and an unfinished loader: the stager
    thread exits, the generator is closed, nothing deadlocks."""
    closed = threading.Event()

    def loader():
        try:
            for i in range(1000):
                yield np.full((8,), i, dtype="float32")
        finally:
            closed.set()

    it = prefetch_to_device(loader(), depth=2)
    assert int(np.asarray(next(it))[0]) == 0
    it.close()
    assert closed.wait(5.0), "generator close() never ran"
    assert not it._thread.is_alive()
    with pytest.raises(StopIteration):
        next(it)
    it.close()  # idempotent


def test_prefetch_close_wakes_blocked_consumer(hvd):
    """close() from ANOTHER thread while the consumer is parked waiting
    on an empty queue must wake the consumer (StopIteration), not leave
    it blocked forever (review finding: the stager exits via the stop
    flag without enqueuing an end marker)."""
    def never_yields():
        time.sleep(30.0)
        yield np.zeros((8,), "float32")

    it = prefetch_to_device(never_yields(), depth=1)
    threading.Timer(0.2, it.close).start()
    t0 = time.time()
    with pytest.raises(StopIteration):
        next(it)
    assert time.time() - t0 < 5.0, "consumer stayed blocked after close()"


def test_prefetch_context_manager_and_break(hvd):
    with prefetch_to_device(_batches(100), depth=2) as it:
        for k, _ in enumerate(it):
            if k == 3:
                break
    assert not it._thread.is_alive()


def test_prefetch_custom_sharding_tree(hvd):
    """Per-leaf PartitionSpec pytrees place each leaf independently
    (the multi-axis shard_parallel_batch layouts)."""
    mesh = horovod_tpu.mesh()
    spec = {"x": P("hvd"), "w": P()}
    def loader():
        yield {"x": np.zeros((8, 2), "float32"),
               "w": np.ones((3,), "float32")}
    got = next(prefetch_to_device(loader(), sharding=spec))
    assert got["x"].sharding == NamedSharding(mesh, P("hvd"))
    assert got["w"].sharding == NamedSharding(mesh, P())


def test_prefetch_host_stall_metric(hvd):
    """A loader slower than the consumer shows up in host.stall_seconds."""
    before = horovod_tpu.metrics().get(
        "host.stall_seconds", {}).get("count", 0)

    def slow():
        for i in range(3):
            time.sleep(0.05)
            yield np.zeros((8,), "float32")

    list(prefetch_to_device(slow(), depth=1))
    snap = horovod_tpu.metrics()["host.stall_seconds"]
    assert snap["count"] > before
    assert snap["sum"] > 0.0


# ---------------------------------------------------------------------------
# Batched device_put satellites
# ---------------------------------------------------------------------------

def test_shard_batch_single_call_tree(hvd):
    """shard_batch is now ONE device_put over the whole tree and must
    preserve the per-leaf values + sharding of the old per-leaf loop."""
    tree = {"a": np.arange(32, dtype="float32").reshape(8, 4),
            "b": (np.ones((8, 2), "int32"), np.zeros((8,), "float32"))}
    out = shard_batch(tree)
    sh = batch_sharding()
    for ref, dev in zip(jax.tree_util.tree_leaves(tree),
                        jax.tree_util.tree_leaves(out)):
        np.testing.assert_array_equal(np.asarray(dev), ref)
        assert dev.sharding == sh


def test_shard_parallel_batch_single_call_specs(hvd):
    mesh = horovod_tpu.mesh()
    batch = (np.zeros((8, 4), "float32"), np.ones((2, 2), "float32"))
    out = shard_parallel_batch(batch, mesh, (P("hvd", None), P()))
    assert out[0].sharding == NamedSharding(mesh, P("hvd", None))
    assert out[1].sharding == NamedSharding(mesh, P())
    # Single-spec broadcast form.
    out2 = shard_parallel_batch(batch[0], mesh, P("hvd"))
    assert out2.sharding == NamedSharding(mesh, P("hvd"))


def test_device_put_batch_defaults(hvd):
    out = device_put_batch({"x": np.zeros((8, 3), "float32")})
    assert out["x"].sharding == batch_sharding()


# ---------------------------------------------------------------------------
# barrier_fence + the async-dispatch step window
# ---------------------------------------------------------------------------

def test_barrier_fence_blocks_on_trees_and_devices(hvd):
    x = jnp.arange(8.0)
    y = jax.jit(lambda a: a * 2)(x)
    barrier_fence(y)          # explicit-tree form
    barrier_fence()           # whole-mesh drain form
    np.testing.assert_array_equal(np.asarray(y), np.arange(8.0) * 2)


def test_train_loop_prefetched_matches_synchronous(hvd):
    """The full overlapped loop (prefetch + deferred fetch + fence) is
    bitwise-identical to the synchronous shard_batch/float(loss) loop."""
    def loss_fn(p, batch):
        return jnp.mean((batch["x"] @ p["w"] - batch["y"]) ** 2)

    opt = optax.sgd(0.05)
    step = make_train_step(loss_fn, opt, donate=False)
    params0 = {"w": jnp.zeros((4, 1))}

    def data(n=8):
        for i in range(n):
            rng = np.random.RandomState(i)
            yield {"x": rng.normal(size=(16, 4)).astype("float32"),
                   "y": rng.normal(size=(16, 1)).astype("float32")}

    # Synchronous leg.
    p_sync, s_sync = params0, opt.init(params0)
    for b in data():
        p_sync, s_sync, loss = step(p_sync, s_sync, shard_batch(b))
        float(loss)

    # Overlapped leg.
    staged0 = hvd.metrics().get("input.batches_staged", {}).get("value", 0)
    p_async, s_async = params0, opt.init(params0)
    with prefetch_to_device(data(), depth=2) as staged:
        for b in staged:
            p_async, s_async, loss = step(p_async, s_async, b)
    barrier_fence(p_async)
    assert (np.asarray(p_sync["w"]).tobytes()
            == np.asarray(p_async["w"]).tobytes())
    # ...and the batches really went through the stager.
    assert hvd.metrics()["input.batches_staged"]["value"] - staged0 == 8


def test_trainer_prefetch_and_log_every(hvd):
    """Trainer.fit's built-in prefetch produces the same history as the
    synchronous path, and log_every hands a fetched loss to the
    callbacks at the chosen cadence only."""
    from horovod_tpu.frontends.loop import Trainer

    def loss_fn(p, batch):
        x, y = batch
        return jnp.mean((x @ p["w"] - y) ** 2)

    def batches(epoch, step):
        rng = np.random.RandomState(epoch * 100 + step)
        return (rng.normal(size=(16, 4)).astype("float32"),
                rng.normal(size=(16, 1)).astype("float32"))

    fetched = []

    class Spy:
        def on_batch_end(self, step, logs=None):
            if logs is not None:
                fetched.append((step, logs["loss"]))

    params0 = {"w": jnp.zeros((4, 1))}
    t1 = Trainer(loss_fn, params0, lr=0.05, callbacks=[Spy()])
    h1 = t1.fit(batches, epochs=2, steps_per_epoch=6, log_every=3)
    assert [s for s, _ in fetched] == [2, 5, 2, 5]
    assert all(np.isfinite(v) for _, v in fetched)

    t2 = Trainer(loss_fn, params0, lr=0.05)
    h2 = t2.fit(batches, epochs=2, steps_per_epoch=6, prefetch=0)
    assert h1 == h2  # overlap reorders host work, never arithmetic
    assert (np.asarray(t1.params["w"]).tobytes()
            == np.asarray(t2.params["w"]).tobytes())


def test_throttled_step_survives_donation(hvd):
    """The in-flight window blocks on PAST outputs whose buffers may
    have been donated into the next dispatch — it must skip the deleted
    leaves instead of raising (the depth>=2 regression)."""
    def loss_fn(p, batch):
        return jnp.mean((batch @ p["w"]) ** 2)

    opt = optax.sgd(0.01)
    step = make_train_step(loss_fn, opt, donate=True)
    params = {"w": jnp.ones((4, 1))}
    opt_state = opt.init(params)
    batch = shard_batch(np.ones((8, 4), "float32"))
    for _ in range(6):  # > window depth: exercises the popleft path
        params, opt_state, loss = step(params, opt_state, batch)
    assert np.isfinite(float(loss))
