"""Worker script for the multi-process tests (launched by horovod_tpu.run).

Each scenario prints a marker line on success; tests/test_multiprocess.py
asserts on the merged rank-prefixed output.  This is the TPU translation of
the reference's ``mpirun -np 2 pytest`` CI leg (.travis.yml:96-123): real
separate processes, real cross-process negotiation.
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def scenario_basic(hvd):
    """Every eager collective across REAL processes, for any world size
    on one host (-np 2 on the CPU; -np 4 with one chip per worker on
    the four-chip TPU host)."""
    import math

    import jax
    import jax.numpy as jnp

    rank, size = hvd.rank(), hvd.size()
    assert size == int(os.environ["HVD_TPU_NUM_PROCESSES"]), size
    # The runtime numbers the processes: on the CPU that is the
    # launcher's HVD_TPU_PROCESS_ID, on a TPU host the position of the
    # worker's chip in the slice (the allgather_object check below sees
    # that the ranks are a permutation either way).
    assert rank == jax.process_index()
    if jax.devices()[0].platform == "cpu":
        assert rank == int(os.environ["HVD_TPU_PROCESS_ID"])
    assert hvd.local_size() == size  # every process on this host
    assert hvd.local_rank() == rank
    assert hvd.cross_size() == 1
    assert hvd.cross_rank() == 0
    tri = size * (size + 1) // 2  # sum of (rank + 1) over the ranks

    # Allreduce: sum and average of genuinely different contributions.
    out = hvd.allreduce(jnp.array([float(rank + 1)] * 4), average=False)
    np.testing.assert_allclose(np.asarray(out), float(tri))
    out = hvd.allreduce(jnp.array([float(rank + 1)] * 4), average=True)
    np.testing.assert_allclose(np.asarray(out), tri / size)

    # Ragged allgather: dim 0 differs per rank (MPI_Allgatherv case).
    mine = jnp.full((rank + 1, 2), float(rank), jnp.float32)
    out = np.asarray(hvd.allgather(mine))
    assert out.shape == (tri, 2), out.shape
    np.testing.assert_allclose(
        out[:, 0], np.repeat(np.arange(size), np.arange(size) + 1))

    # Broadcast from a non-zero root.
    out = hvd.broadcast(jnp.array([float(rank)] * 3), root_rank=1)
    np.testing.assert_allclose(np.asarray(out), 1.0)

    # Async + fusion: several small allreduces in flight together.
    hs = [hvd.allreduce_async(jnp.array([float(rank + i)]), average=False,
                              name=f"fused.{i}") for i in range(4)]
    for i, h in enumerate(hs):
        np.testing.assert_allclose(np.asarray(hvd.synchronize(h)),
                                   float(size * i + tri - size))

    # Sparse allreduce (IndexedSlices -> allgather of values+indices,
    # the reference's tensorflow/__init__.py:67-78 path) across REAL
    # processes: rank r contributes row r with value r+1.
    from horovod_tpu import IndexedSlices
    from horovod_tpu.ops.sparse import as_dense

    sl = IndexedSlices(jnp.full((1, 2), float(rank + 1), jnp.float32),
                       jnp.array([rank], jnp.int32), (size, 2))
    out = hvd.allreduce(sl, average=False, name="sparse.op")
    np.testing.assert_allclose(
        np.asarray(as_dense(out)),
        np.repeat(np.arange(1.0, size + 1)[:, None], 2, axis=1))

    # Reduce operators across REAL processes (post-v0.13 op= API):
    # rank r contributes r+1, so min/max/product are all distinct; the
    # adasum of mutually orthogonal vectors is their sum; mismatched
    # ops for one name must fail validation on every rank.
    x = jnp.array([float(rank + 1)])
    assert float(hvd.allreduce(x, op=hvd.Min, name="red.min")[0]) == 1.0
    assert float(hvd.allreduce(x, op=hvd.Max,
                               name="red.max")[0]) == float(size)
    assert float(hvd.allreduce(x, op=hvd.Product, name="red.prod")[0]) \
        == float(math.factorial(size))
    if size & (size - 1) == 0:  # the ladder needs a power of two
        ada = hvd.allreduce(
            jnp.zeros((size,)).at[rank].set(float(rank + 1)),
            op=hvd.Adasum, name="red.adasum")
        np.testing.assert_allclose(np.asarray(ada),
                                   np.arange(1.0, size + 1), rtol=1e-6)
    from horovod_tpu import HorovodError as _HErr

    try:
        hvd.allreduce(x, op=hvd.Min if rank == 0 else hvd.Max,
                      name="red.bad")
        raise AssertionError("mismatched reduce ops did not raise")
    except _HErr as e:
        assert "Mismatched reduce operations" in str(e), str(e)

    # Reducescatter across REAL processes (post-v0.13): each rank gets
    # its own chunk of the reduction of (arange + rank).
    out = hvd.reducescatter(jnp.arange(2.0 * size) + rank, average=False,
                            name="red.rscatter")
    want = (size * np.arange(2.0 * size)
            + (tri - size))[2 * rank:2 * rank + 2]
    np.testing.assert_allclose(np.asarray(out), want)
    out = hvd.reducescatter(jnp.arange(2.0 * size) + rank, average=True,
                            name="red.rscatter.avg")
    np.testing.assert_allclose(np.asarray(out), want / size)

    # Alltoall across REAL processes (post-v0.13), ragged splits:
    # sender s ships 1 + (s + d) % 2 rows to destination d, tagged
    # 100*s + its running row index.  Receiver d concatenates in
    # sender order.
    def splits(s):
        return [1 + (s + d) % 2 for d in range(size)]

    def rows(s):
        return np.arange(float(sum(splits(s)))) + 100 * s

    out = np.asarray(hvd.alltoall(jnp.asarray(rows(rank).reshape(-1, 1)),
                                  splits=splits(rank), name="red.a2a"))
    want = []
    for s in range(size):
        lo = sum(splits(s)[:rank])
        want.extend(rows(s)[lo:lo + splits(s)[rank]])
    np.testing.assert_allclose(out[:, 0], want)
    hvd.barrier()

    # Object collectives across REAL processes: per-rank pickles of
    # genuinely different sizes ride the ragged allgather; broadcast
    # ships the root's object to the non-roots.
    from horovod_tpu import allgather_object, broadcast_object

    objs = allgather_object({"rank": rank, "pad": "x" * (10 * rank)})
    assert [o["rank"] for o in objs] == list(range(size)), objs
    assert [len(o["pad"]) for o in objs] == \
        [10 * r for r in range(size)]
    got = broadcast_object({"resume": 7} if rank == 0 else None,
                           root_rank=0)
    assert got == {"resume": 7}, got
    print(f"BASIC_OK rank={rank}")


def scenario_mismatch(hvd):
    import jax.numpy as jnp

    from horovod_tpu import HorovodError

    rank = hvd.rank()
    # Real cross-rank disagreement: different shapes for the same name.
    x = jnp.zeros((2 + rank,), jnp.float32)
    try:
        hvd.allreduce(x, name="bad.shape")
    except HorovodError as e:
        assert "Mismatched allreduce tensor shapes" in str(e), str(e)
        print(f"MISMATCH_OK rank={rank}")
        return
    raise AssertionError("mismatched allreduce did not raise")


def scenario_stall(hvd):
    import jax.numpy as jnp

    rank = hvd.rank()
    threshold = float(os.environ["HOROVOD_STALL_WARNING_SECONDS"])
    if rank == 0:
        h = hvd.allreduce_async(jnp.ones((2,)), name="late.op",
                                average=False)
        # Worker 1 sits out past the stall threshold; the coordinator's
        # background tick must print a warning naming it.
        out = hvd.synchronize(h)  # completes once rank 1 finally submits
        np.testing.assert_allclose(np.asarray(out), 2.0)
    else:
        time.sleep(3.0 * threshold)
        out = hvd.allreduce(jnp.ones((2,)), name="late.op", average=False)
        np.testing.assert_allclose(np.asarray(out), 2.0)
    print(f"STALL_OK rank={rank}")


def scenario_shutdown(hvd):
    import jax.numpy as jnp

    from horovod_tpu import HorovodError

    rank = hvd.rank()
    if rank == 0:
        # This op can never complete: rank 1 shuts down instead of
        # submitting.  The SHUTDOWN it triggers must poison the handle.
        h = hvd.allreduce_async(jnp.ones((2,)), name="doomed.op",
                                average=False)
        try:
            hvd.synchronize(h)
        except HorovodError as e:
            assert "shut down" in str(e), str(e)
            print(f"SHUTDOWN_OK rank={rank}")
            return
        raise AssertionError("shutdown did not poison the pending op")
    else:
        time.sleep(1.0)
        hvd.shutdown()
        print(f"SHUTDOWN_OK rank={rank}")


def scenario_dead_worker(hvd):
    import jax.numpy as jnp

    from horovod_tpu import HorovodError

    rank = hvd.rank()
    # Barrier first so every rank is fully initialized and connected
    # before the victim dies — otherwise, under machine load, the death
    # can land mid-startup on a slow survivor and surface as a different
    # error than the pending-op diagnosis this test is about.
    hvd.allreduce(jnp.ones((1,)), name="pre.death.barrier", average=False)
    # The last rank dies; EVERY survivor (controller and plain workers
    # alike) must get a diagnosed failure and exit promptly.
    if rank < hvd.size() - 1:
        h = hvd.allreduce_async(jnp.ones((2,)), name="orphaned.op",
                                average=False)
        try:
            hvd.synchronize(h)
        except HorovodError as e:
            assert "terminated unexpectedly" in str(e), str(e)
            print(f"DEADWORKER_OK rank={rank}")
            return
        raise AssertionError("dead worker was not detected")
    else:
        time.sleep(1.0)
        os._exit(0)  # die without any shutdown handshake


def scenario_torch_frontend(hvd):
    """The Torch frontend across REAL processes: eager tensor
    collectives and DistributedOptimizer gradient averaging ride the
    TCP control plane (the reference's torch CI leg under mpirun)."""
    import torch
    import torch.nn as nn

    import horovod_tpu.frontends.torch as thvd

    rank, size = hvd.rank(), hvd.size()
    out = thvd.allreduce(torch.full((3,), float(rank + 1)), average=True,
                         name="t.avg")
    np.testing.assert_allclose(out.numpy(), 1.5)

    model = nn.Linear(2, 1, bias=False)
    with torch.no_grad():
        model.weight.fill_(float(rank))  # divergent start
    thvd.broadcast_parameters(model.state_dict(), root_rank=0)
    np.testing.assert_allclose(model.weight.detach().numpy(), 0.0)

    opt = torch.optim.SGD(model.parameters(), lr=1.0)
    opt = thvd.DistributedOptimizer(
        opt, named_parameters=model.named_parameters())
    # Rank-dependent inputs so per-rank gradients genuinely differ and
    # the averaged update is checkable by hand on every rank.
    x = torch.full((4, 2), float(rank + 1))
    y = torch.ones((4, 1))
    opt.zero_grad()
    loss = ((model(x) - y) ** 2).mean()
    loss.backward()
    opt.step()
    # With w=0: grad_r = 2*mean_i(x_i*(0-1)) = -2*(r+1) per component;
    # averaged over ranks r=0..size-1: -2*mean(r+1) = -(size+1).
    want = (2.0 * np.mean([r + 1 for r in range(size)])) * 1.0
    np.testing.assert_allclose(model.weight.detach().numpy(), want,
                               rtol=1e-5)

    # broadcast_optimizer_state across REAL processes: non-root starts
    # with a divergent lr AND no momentum buffers; root's full
    # state_dict (momentum included) must land.
    m2 = nn.Linear(2, 1, bias=False)
    o2 = torch.optim.SGD(m2.parameters(), lr=0.5, momentum=0.9)
    if rank == 0:
        ((m2(torch.ones(1, 2))).sum()).backward()
        o2.step()  # creates the momentum buffer on root only
    else:
        o2.param_groups[0]["lr"] = 99.0
    thvd.broadcast_optimizer_state(o2, root_rank=0)
    assert o2.param_groups[0]["lr"] == 0.5, o2.param_groups[0]["lr"]
    assert any("momentum_buffer" in st
               for st in o2.state_dict()["state"].values())

    # SyncBatchNorm across REAL processes: each rank normalizes ITS half
    # of a batch with statistics spanning BOTH halves — output, input
    # gradients, and running stats must match stock BatchNorm1d applied
    # to the full batch (the defining property; per-rank BN would use
    # divergent means).
    g = torch.Generator().manual_seed(7)
    full = torch.randn(8, 3, generator=g) * 2.0 + 1.0
    gout = torch.randn(8, 3, generator=g)
    half = full[rank * 4:(rank + 1) * 4].clone().requires_grad_(True)
    sbn = thvd.SyncBatchNorm(3, momentum=0.4)
    out = sbn(half)
    out.backward(gout[rank * 4:(rank + 1) * 4])

    ref_in = full.clone().requires_grad_(True)
    ref = torch.nn.BatchNorm1d(3, momentum=0.4)
    ref_out = ref(ref_in)
    ref_out.backward(gout)
    np.testing.assert_allclose(
        out.detach().numpy(),
        ref_out.detach().numpy()[rank * 4:(rank + 1) * 4], atol=1e-5)
    np.testing.assert_allclose(
        half.grad.numpy(),
        ref_in.grad.numpy()[rank * 4:(rank + 1) * 4], atol=1e-5)
    np.testing.assert_allclose(sbn.running_mean.numpy(),
                               ref.running_mean.numpy(), atol=1e-5)
    np.testing.assert_allclose(sbn.running_var.numpy(),
                               ref.running_var.numpy(), atol=1e-4)
    print(f"TORCH_OK rank={rank}")


def scenario_spmd_train(hvd):
    """The static fast path across REAL processes: one jitted SPMD train
    step over the global (2-process) mesh.  Verifies (a) training works
    and losses agree bit-for-bit on every rank, and (b) the
    ``shard_local_batch`` input model — each process contributing only
    its own rows — produces the same global batch as every host holding
    the full array (``shard_batch``)."""
    import jax
    import jax.numpy as jnp
    import optax

    from horovod_tpu.parallel.training import (make_train_step,
                                               shard_batch,
                                               shard_local_batch)

    rank, size = hvd.rank(), hvd.size()
    w_true = jnp.array([2.0, -3.0])
    X = np.asarray(jax.random.normal(jax.random.PRNGKey(0), (64, 2)))
    y = np.asarray(X @ np.asarray(w_true))

    params = {"w": jnp.zeros((2,))}
    params = hvd.broadcast_parameters(params, root_rank=0)
    opt = optax.sgd(0.1)

    def loss_fn(p, batch):
        xb, yb = batch
        return jnp.mean((xb @ p["w"] - yb) ** 2)

    step = make_train_step(loss_fn, opt)
    # Per-process input pipeline: this rank loads ONLY its rows.
    n_local = len(X) // size
    lo = rank * n_local
    batch = shard_local_batch((X[lo:lo + n_local], y[lo:lo + n_local]))
    opt_state = opt.init(params)
    for _ in range(30):
        params, opt_state, loss = step(params, opt_state, batch)
    final = float(loss)
    assert final < 1e-3, final
    # Bit-for-bit agreement across ranks: summing identical f32 values
    # over 2 ranks is exact, so any divergence breaks the equality.
    total = float(np.asarray(hvd.allreduce(jnp.array([final]),
                                           average=False,
                                           name="spmd.final.loss"))[0])
    assert total == size * final, (total, final)

    # Equivalence: the full-global-array path yields the same first-step
    # loss from the same start (both spell the identical global batch).
    p0 = {"w": jnp.zeros((2,))}
    s0 = opt.init(p0)
    _, _, l_local = step(p0, s0, batch)
    p0 = {"w": jnp.zeros((2,))}
    s0 = opt.init(p0)
    _, _, l_global = step(p0, s0, shard_batch((X, y)))
    np.testing.assert_array_equal(np.asarray(l_local), np.asarray(l_global))
    print(f"SPMD_OK rank={rank} loss={final:.6f}")


def scenario_overlap(hvd):
    """Multi-process bucketed streaming (ISSUE 12 tentpole a): the
    overlapped np=2 train step — per-bucket partial cycles negotiated
    over the REAL TCP control plane, mp megakernel reductions,
    take_async feeding in-flight results into the apply — is
    BITWISE-identical to the serial mp schedule (same sub-programs,
    fenced) and within float tolerance of the monolithic mp step (the
    identity contract of parallel/overlap.py), for both the plain
    (single-backward) and the ChainedLoss (segmented) schedule; on the
    steady state every bucket replays from the response cache with
    ZERO new negotiation misses."""
    import jax
    import jax.numpy as jnp
    import optax

    import horovod_tpu.telemetry as _tel
    from horovod_tpu.core import state as _st
    from horovod_tpu.parallel.overlap import ChainedLoss
    from horovod_tpu.parallel.training import (make_train_step,
                                               shard_local_batch)

    rank, size = hvd.rank(), hvd.size()
    D = 16

    def stage0(p, carry, b):
        x, _y = b
        return jnp.tanh(x @ p["w"] + p["b"])

    def stage1(p, carry, b):
        _x, y = b
        pred = carry @ p["w"] + p["b"]
        return jnp.mean((pred - y) ** 2)

    chain = ChainedLoss([stage0, stage1])

    def plain_loss(p, b):
        return chain(p, b)

    ks = jax.random.split(jax.random.PRNGKey(0), 2)
    params0 = [{"w": jax.random.normal(k, (D, D)) * D ** -0.5,
                "b": jnp.zeros((D,))} for k in ks]
    X = np.asarray(jax.random.normal(jax.random.PRNGKey(1), (8 * size, D)),
                   dtype="float32")
    Y = np.asarray(jax.random.normal(jax.random.PRNGKey(2), (8 * size, D)),
                   dtype="float32")
    lo = rank * (len(X) // size)
    batch = shard_local_batch((X[lo:lo + len(X) // size],
                               Y[lo:lo + len(Y) // size]))
    opt = optax.adam(1e-3)
    threshold = D * D * 4  # w and b bucket apart per stage

    def run(step, steps=4):
        p, s = params0, opt.init(params0)
        loss = None
        for _ in range(steps):
            p, s, loss = step(p, s, batch)
        jax.block_until_ready(jax.tree_util.tree_leaves(p))
        return p, float(loss)

    def leaves_equal(a, b):
        return all(
            np.asarray(u).tobytes() == np.asarray(v).tobytes()
            for u, v in zip(jax.tree_util.tree_leaves(a),
                            jax.tree_util.tree_leaves(b)))

    fallbacks0 = _tel.metrics().get(
        "overlap.fallbacks", {}).get("value", 0)

    def leaves_close(a, b):
        return all(
            np.allclose(np.asarray(u), np.asarray(v), rtol=1e-4,
                        atol=1e-5)
            for u, v in zip(jax.tree_util.tree_leaves(a),
                            jax.tree_util.tree_leaves(b)))

    def build(loss, mode):
        return make_train_step(loss, opt, donate=False,
                               fusion_threshold=threshold, overlap=mode)

    # Leg 1 — segmented schedule (ChainedLoss): streamed mp partial
    # cycles ≡ the serial mp schedule, bitwise after 4 adam steps, and
    # ≈ the monolithic mp step.
    step_on = build(chain, "on")
    p_on, l_on = run(step_on)
    assert step_on.overlap_active, "mp build fell back"
    assert step_on.segment_count == 2
    assert step_on.bucket_count == 4
    step_serial = build(chain, "serial")
    p_ser, l_ser = run(step_serial)
    assert l_on == l_ser, (l_on, l_ser)
    assert leaves_equal(p_on, p_ser), "streamed mp != serial mp"
    p_off, _ = run(build(chain, "off"))
    assert leaves_close(p_on, p_off), "overlapped mp !~ monolithic mp"
    print(f"OVERLAP_SEG_OK rank={rank} loss={l_on:.6f}")

    # Leg 2 — plain loss (single-backward streaming): same contract.
    step_u_on = build(plain_loss, "on")
    p_u_on, _ = run(step_u_on, 2)
    assert step_u_on.overlap_active
    p_u_ser, _ = run(build(plain_loss, "serial"), 2)
    assert leaves_equal(p_u_on, p_u_ser)
    p_u_off, _ = run(build(plain_loss, "off"), 2)
    assert leaves_close(p_u_on, p_u_off)
    print(f"OVERLAP_PLAIN_OK rank={rank}")

    # Leg 3 — steady state: every bucket's partial cycle replays from
    # the response cache; two further steps add ZERO negotiation
    # misses on either rank, and the mp bucket counter advances.
    st = _st.global_state()
    cache = st.response_cache
    assert cache is not None
    misses0 = cache.stats.misses
    mp0 = _tel.metrics().get(
        "overlap.mp_buckets_dispatched", {}).get("value", 0)
    p, s = p_on, opt.init(p_on)
    for _ in range(2):
        p, s, _loss = step_on(p, s, batch)
    jax.block_until_ready(jax.tree_util.tree_leaves(p))
    assert cache.stats.misses == misses0, (
        f"steady-state mp buckets renegotiated: "
        f"{cache.stats.misses - misses0} new misses")
    mp_buckets = _tel.metrics()[
        "overlap.mp_buckets_dispatched"]["value"] - mp0
    assert mp_buckets == 2 * step_on.bucket_count, mp_buckets
    fallbacks = _tel.metrics().get(
        "overlap.fallbacks", {}).get("value", 0) - fallbacks0
    assert fallbacks == 0, f"{fallbacks} unexpected overlap fallbacks"

    # Leg 4 — transport fault MID-PARTIAL-CYCLE: rank 1's control-plane
    # socket is hard-reset right before a training step, so the very
    # next bucket's coalesced request frame hits the dead socket
    # mid-flush; the session-resume protocol replays the lost frames
    # (cache replicas stay index-aligned) and the trained parameters
    # stay BITWISE-identical to the uninterrupted serial run — the
    # no-new-hang-class contract for partial cycles.
    p, s = params0, opt.init(params0)
    for stepi in range(6):
        if stepi == 3 and rank == 1:
            from horovod_tpu.ops import transport as _tp

            _tp._hard_close(st.transport._sock)
        p, s, _loss = step_on(p, s, batch)
    jax.block_until_ready(jax.tree_util.tree_leaves(p))
    q, t = params0, opt.init(params0)
    for _ in range(6):
        q, t, _loss = step_serial(q, t, batch)
    jax.block_until_ready(jax.tree_util.tree_leaves(q))
    assert leaves_equal(p, q), \
        "post-reconnect overlapped params != uninterrupted serial"
    if rank == 1:
        got = _tel.metrics().get("transport.reconnects",
                                 {}).get("value", 0)
        assert got >= 1, f"no reconnect was recorded: {got}"
    print(f"OVERLAP_OK rank={rank} buckets={mp_buckets}")


def scenario_chaos(hvd):
    """hvd-chaos acceptance (ISSUE 9): a worker's control-plane
    connection dies mid-training; the worker reconnects with backoff,
    the session-resume protocol replays the lost frames (re-syncing its
    response-cache replica), and training completes BITWISE-identical
    to the uninterrupted run — replayed in numpy below with the exact
    same f32 arithmetic."""
    import jax.numpy as jnp

    rank, size = hvd.rank(), 2
    assert hvd.size() == size
    w_true = np.array([1.5, -2.0], dtype="float32")
    rng = np.random.RandomState(5 + rank)
    X = rng.normal(size=(16, 2)).astype("float32")
    y = X @ w_true
    w = np.zeros(2, dtype="float32")
    steps = 20
    for step in range(steps):
        if step == 10 and rank == 1:
            # Transient network fault: hard-reset THIS rank's
            # control-plane socket mid-run (the chaos transport.reset
            # wire effect, applied directly so the firing point is
            # exact).  The reconnect path must absorb it.
            from horovod_tpu.core import state as _st
            from horovod_tpu.ops import transport as _tp

            _tp._hard_close(_st.global_state().transport._sock)
        g = (2.0 * X.T @ (X @ w - y) / len(X)).astype("float32")
        g_avg = np.asarray(hvd.allreduce(
            jnp.asarray(g), average=True, name=f"chaos.g.{step}"))
        w = (w - 0.1 * g_avg).astype("float32")

    # The uninterrupted run, replayed in f32 numpy.
    datas = []
    for r in range(size):
        rr = np.random.RandomState(5 + r)
        Xr = rr.normal(size=(16, 2)).astype("float32")
        datas.append((Xr, Xr @ w_true))
    we = np.zeros(2, dtype="float32")
    for _ in range(steps):
        gs = [(2.0 * Xr.T @ (Xr @ we - yr) / len(Xr)).astype("float32")
              for Xr, yr in datas]
        we = (we - 0.1 * ((gs[0] + gs[1]) / 2.0)).astype("float32")
    np.testing.assert_array_equal(w, we)

    if rank == 1:
        import horovod_tpu.telemetry as _tel

        snap = _tel.metrics()
        got = snap.get("transport.reconnects", {}).get("value", 0)
        assert got >= 1, f"no reconnect was recorded: {got}"
    print(f"CHAOS_MP_OK rank={rank} w=[{w[0]:.6f},{w[1]:.6f}]")


def scenario_dead_controller(hvd):
    """Rank 0 (the controller) dies without any handshake.  Rank 0 also
    hosts the jax coordination service, so jax's client usually
    fatal-kills the worker the instant the service socket closes; when
    our transport's EOF detection wins that race instead, the pending op
    fails with the controller-death diagnosis.  Either way the worker
    must terminate promptly — the launch-level assertion."""
    import jax.numpy as jnp

    from horovod_tpu import HorovodError

    rank = hvd.rank()
    if rank == 0:
        time.sleep(1.0)
        os._exit(0)  # controller dies without any shutdown handshake
    else:
        h = hvd.allreduce_async(jnp.ones((2,)), name="orphaned.op",
                                average=False)
        try:
            hvd.synchronize(h)
        except HorovodError as e:
            assert "controller terminated unexpectedly" in str(e), str(e)
            print(f"DEADCTRL_OK rank={rank}")
            return
        raise AssertionError("dead controller was not detected")


def scenario_clean_exit(hvd):
    """Rank 1 finishes WITHOUT calling hvd.shutdown(): the transport's
    atexit handshake must turn the interpreter exit into a cooperative
    shutdown — rank 0 gets the plain shut-down error (no crash
    diagnosis), and both processes still exit rc=0 through
    jax.distributed's exit barrier."""
    import jax.numpy as jnp

    from horovod_tpu import HorovodError

    rank = hvd.rank()
    out = hvd.allreduce(jnp.ones((2,)), name="warm.op", average=False)
    np.testing.assert_allclose(np.asarray(out), 2.0)
    if rank == 1:
        main.skip_shutdown = True
        print("CLEANEXIT_OK rank=1")
        return  # interpreter exit fires the handshake
    try:
        hvd.allreduce(jnp.ones((2,)), name="late.op", average=False)
        raise AssertionError("expected the shut-down error")
    except HorovodError as e:
        assert "terminated unexpectedly" not in str(e), str(e)
        print("CLEANEXIT_OK rank=0")


def scenario_tf_function(hvd):
    """Compiled-graph collectives across REAL processes (round 4): a
    tf.function-compiled step allreduces mid-graph through the
    py_function bridge — the TF2 spelling of the reference's
    session.run(train_op) with AsyncOpKernels enqueueing from graph
    execution (mpi_ops.cc:270-298)."""
    import tensorflow as tf

    import horovod_tpu.frontends.tensorflow as hvdtf

    rank = hvd.rank()

    @tf.function
    def f(x):
        return hvdtf.allreduce(x, average=False, name="tffn.op")

    for i in range(3):  # repeated executions reuse the trace-time name
        out = f(tf.constant([float(rank + 1 + i)]))
        np.testing.assert_allclose(out.numpy(), [3.0 + 2.0 * i])

    w = tf.Variable([0.0])

    @tf.function
    def train_step():
        with hvdtf.DistributedGradientTape(tf.GradientTape()) as tape:
            # Rank-dependent loss: grad_r = 2*(w - (r+1)); averaged over
            # the 2 ranks: 2*(w - 1.5) — the compiled update must use
            # the REDUCED gradient identically on both ranks.
            loss = (w[0] - float(rank + 1)) ** 2
        (g,) = tape.gradient(loss, [w])
        w.assign_sub(0.25 * g)
        return loss

    for _ in range(25):
        train_step()
    np.testing.assert_allclose(w.numpy(), [1.5], atol=1e-3)
    print(f"TFFN_OK rank={rank}")


def _sync_expect_abandoned(hvd, h, who, t0: float, budget: float = 20.0):
    """synchronize(h) with a short timeout, expecting the coordinator's
    group-wide abandonment ERROR (not the local-fallback timeout text).
    ``who`` pins the named withdrawing rank, or None when several ranks
    race and the winner is nondeterministic.  The short timeout applies
    ONLY to this call — the env is read per call, so recovery
    collectives and co-launched scenarios keep the default."""
    from horovod_tpu import HorovodError

    prev = os.environ.get("HOROVOD_TPU_SYNC_TIMEOUT")
    os.environ["HOROVOD_TPU_SYNC_TIMEOUT"] = "2"
    try:
        hvd.synchronize(h)
        raise AssertionError("expected the withdrawal error")
    except HorovodError as e:
        want = ("was abandoned: rank" if who is None
                else f"was abandoned: rank {who}")
        assert want in str(e), str(e)
    finally:
        if prev is None:
            os.environ.pop("HOROVOD_TPU_SYNC_TIMEOUT", None)
        else:
            os.environ["HOROVOD_TPU_SYNC_TIMEOUT"] = prev
    assert time.monotonic() - t0 < budget, "fail-fast regressed"


def scenario_withdraw(hvd):
    """A rank whose synchronize times out WITHDRAWS the op group-wide:
    the coordinator broadcasts an ERROR response and the op fails on
    every rank within the grace window — instead of the round-3 behavior
    (local-only withdrawal; peers later execute a response the withdrawer
    skips, or serially eat their own 300 s timeouts).  The failure is
    surgical: the group survives and later collectives work."""
    import jax.numpy as jnp

    rank = hvd.rank()

    # Leg 1 — a WORKER (rank 1) gives up: the WITHDRAW frame rides the
    # TCP control plane to the coordinator.
    t0 = time.monotonic()
    if rank == 1:
        h = hvd.allreduce_async(jnp.ones((2,)), name="abandoned.w",
                                average=False)
        _sync_expect_abandoned(hvd, h, 1, t0)
    else:
        time.sleep(4.0)  # outlive the peer's timeout; never submit
    out = hvd.allreduce(jnp.ones((2,)), name="recover.w", average=False)
    np.testing.assert_allclose(np.asarray(out), 2.0)

    # Leg 2 — the CONTROLLER (rank 0) gives up: withdrawal goes straight
    # into the in-process coordinator, ERROR still broadcasts to all.
    t1 = time.monotonic()
    if rank == 0:
        h = hvd.allreduce_async(jnp.ones((2,)), name="abandoned.c",
                                average=False)
        _sync_expect_abandoned(hvd, h, 0, t1)
    else:
        time.sleep(4.0)
    out = hvd.allreduce(jnp.ones((2,)), name="recover.c", average=False)
    np.testing.assert_allclose(np.asarray(out), 2.0)
    print(f"WITHDRAW_OK rank={rank}")


def scenario_checkpoint(hvd):
    import jax.numpy as jnp

    from horovod_tpu.utils.checkpoint import (restore_checkpoint,
                                              resume_epoch,
                                              save_checkpoint)

    rank = hvd.rank()
    path = os.environ["HVD_TPU_TEST_CKPT"]
    good = {"w": np.full((3,), 7.0, "float32")}
    if rank == 0:
        assert save_checkpoint(path, good, step=5)
    else:
        # Non-root never writes (reference rank-0 convention).
        assert not save_checkpoint(path, {"w": np.zeros((3,))}, step=5)
    while not os.path.exists(path):
        time.sleep(0.05)
    # Each rank starts from divergent state; restore must converge all
    # ranks to root's values via the broadcast.
    mine = {"w": jnp.full((3,), float(rank + 1))}
    restored = restore_checkpoint(path, mine)
    np.testing.assert_allclose(np.asarray(restored["w"]), 7.0)
    assert resume_epoch(path) == 5
    print(f"CKPT_OK rank={rank}")


def scenario_join(hvd):
    """hvd.join() across REAL processes (post-v0.13 API; the v0.13
    reference could only hang on uneven workloads): rank 0 runs out of
    data after 2 steps, rank 1 trains 4; the joined rank contributes
    zeros until everyone joins; both learn the last joining rank.  The
    barrier is reusable, and a broadcast whose root has joined fails
    with a clean diagnosis instead of hanging."""
    import jax.numpy as jnp

    from horovod_tpu import HorovodError

    rank = hvd.rank()
    steps = 2 if rank == 0 else 4
    for i in range(steps):
        out = hvd.allreduce(jnp.full((3,), float(rank + 1)),
                            average=False, name=f"join.step.{i}")
        want = 3.0 if i < 2 else 2.0  # rank 0 joined: zeros + rank 1's 2
        np.testing.assert_allclose(np.asarray(out), want)
        if i >= 2:
            # Ragged allgather with a joined rank: 0 rows from rank 0.
            g = hvd.allgather(jnp.full((2, 2), 7.0),
                              name=f"join.gather.{i}")
            assert np.asarray(g).shape == (2, 2), g.shape
            np.testing.assert_allclose(np.asarray(g), 7.0)
    assert hvd.join() == 1  # rank 1 joins last (it had more batches)

    # The barrier is reusable; a joined root is a clean error.
    if rank == 0:
        assert hvd.join() == 1
    else:
        try:
            hvd.broadcast(jnp.ones((2,)), root_rank=0, name="joined.root")
            raise AssertionError("expected the joined-root error")
        except HorovodError as e:
            assert "has joined" in str(e), str(e)
        assert hvd.join() == 1
    out = hvd.allreduce(jnp.ones((2,)), name="post.join", average=False)
    np.testing.assert_allclose(np.asarray(out), 2.0)

    # Round 3 of the barrier: an async op outstanding ACROSS join().  It
    # can FUSE with a tensor completed by this rank's join, so the
    # joined rank must execute the mixed buffer — its real value in its
    # own slot, zeros in the peer-only slot — identically to the peers'
    # fused flat buffer (round-4 review finding).  (Fusion of the two
    # tensors depends on them becoming ready within one 5 ms tick —
    # overwhelmingly likely with back-to-back submits; if they miss, the
    # assertions still hold via unfused responses.)
    if rank == 0:
        h = hvd.allreduce_async(jnp.full((4,), 1.0), name="fuse.mine",
                                average=False)
        assert hvd.join() == 1
        np.testing.assert_allclose(np.asarray(hvd.synchronize(h)), 3.0)
    else:
        time.sleep(0.5)  # rank 0's submit + JOIN land first
        ha = hvd.allreduce_async(jnp.full((4,), 2.0), name="fuse.mine",
                                 average=False)
        hb = hvd.allreduce_async(jnp.full((2,), 5.0), name="fuse.peer",
                                 average=False)
        np.testing.assert_allclose(np.asarray(hvd.synchronize(ha)), 3.0)
        np.testing.assert_allclose(np.asarray(hvd.synchronize(hb)), 5.0)
        assert hvd.join() == 1
    print(f"JOIN_OK rank={rank}")


def scenario_process_sets(hvd):
    """Process sets across REAL processes (post-v0.13 API; the v0.13
    reference fixes everything to MPI_COMM_WORLD): np=3, set {0,2}
    negotiates and executes over its own sub-mesh while rank 1 runs a
    disjoint singleton set, then everyone meets again in a global op.
    Registration is collective and validated; a non-member submit
    raises."""
    import jax.numpy as jnp

    from horovod_tpu import HorovodError

    rank, size = hvd.rank(), hvd.size()
    assert size == 3, size
    ps = hvd.add_process_set([0, 2])
    assert ps.included() == (rank in (0, 2))
    if ps.included():
        out = hvd.allreduce(jnp.full((2,), float(rank + 1)),
                            average=False, process_set=ps, name="ps.sum")
        np.testing.assert_allclose(np.asarray(out), 4.0)  # ranks 0+2: 1+3
        out = hvd.allreduce(jnp.full((2,), float(rank + 1)),
                            average=True, process_set=ps, name="ps.avg")
        np.testing.assert_allclose(np.asarray(out), 2.0)
        # Ragged allgather inside the set: member m contributes m+1 rows.
        mine = jnp.full((ps.rank() + 1, 2), float(rank))
        g = np.asarray(hvd.allgather(mine, process_set=ps,
                                     name="ps.gather"))
        assert g.shape == (3, 2), g.shape
        np.testing.assert_allclose(g[:1], 0.0)
        np.testing.assert_allclose(g[1:], 2.0)
        # Broadcast rooted at GLOBAL rank 2 (set-local 1).
        out = hvd.broadcast(jnp.full((2,), float(rank)), 2,
                            process_set=ps, name="ps.bcast")
        np.testing.assert_allclose(np.asarray(out), 2.0)
    else:
        try:
            hvd.allreduce(jnp.ones((2,)), process_set=ps, name="ps.bad")
            raise AssertionError("non-member submit did not raise")
        except HorovodError as e:
            assert "not a member" in str(e), str(e)
    # A second, disjoint set keeps its own coordinator and sub-mesh.
    ps1 = hvd.add_process_set([1])
    if rank == 1:
        out = hvd.allreduce(jnp.array([5.0]), average=False,
                            process_set=ps1, name="ps1.solo")
        np.testing.assert_allclose(np.asarray(out), 5.0)
    # AUTO-NAMED ops: set members consumed set-namespaced names, so an
    # unnamed GLOBAL op right after must still agree across ALL ranks
    # (review finding: a shared counter would desync members from
    # non-members and stall/misroute here).
    if ps.included():
        out = hvd.allreduce(jnp.ones((2,)), average=False, process_set=ps)
        np.testing.assert_allclose(np.asarray(out), 2.0)
    out = hvd.allreduce(jnp.full((2,), 2.0), average=False)  # unnamed
    np.testing.assert_allclose(np.asarray(out), 2.0 * size)
    # Chaining a set output into a global collective re-places it.
    if ps.included():
        chained = hvd.allreduce(jnp.ones((2,)), average=False,
                                process_set=ps, name="ps.chain")
    else:
        chained = jnp.full((2,), 2.0)
    out = hvd.allreduce(chained, average=False, name="ps.chain.world")
    np.testing.assert_allclose(np.asarray(out), 6.0)
    # And the global set still works for everyone afterwards.
    out = hvd.allreduce(jnp.ones((2,)), average=False, name="ps.world")
    np.testing.assert_allclose(np.asarray(out), float(size))
    print(f"PSETS_OK rank={rank}")


def scenario_elastic(hvd):
    """Elastic relaunch across REAL processes: rank 1 dies hard at step
    5 of the first incarnation; rank 0 diagnoses the dead peer, exits
    EX_TEMPFAIL, and the --elastic launcher relaunches the job.  The
    second incarnation resumes from the last commit (step 4) and must
    converge to EXACTLY the weights of an uninterrupted run — the test
    replays the arithmetic in numpy and compares."""
    import jax.numpy as jnp

    from horovod_tpu import elastic

    rank = hvd.rank()
    edir = os.environ["HVD_TPU_ELASTIC_DIR"]
    marker = os.path.join(edir, "victim_died")
    total = 8

    w_true = np.array([1.0, -2.0], dtype="float32")
    rng = np.random.RandomState(17 + rank)
    X = rng.normal(size=(total, 16, 2)).astype("float32")
    y = X @ w_true

    state = elastic.State(w=jnp.zeros((2,)), step=0)

    @elastic.run
    def train(state):
        if state.step > 0:
            print(f"ELASTIC_RESUMED rank={rank} step={state.step}")
        while state.step < total:
            i = state.step
            if rank == 1 and i == 5 and not os.path.exists(marker):
                open(marker, "w").close()
                os._exit(1)  # hard failure, no handshake
            xb, yb = jnp.asarray(X[i]), jnp.asarray(y[i])
            grad = 2.0 * xb.T @ (xb @ state.w - yb) / xb.shape[0]
            grad = hvd.allreduce(grad, average=True, name=f"el.grad.{i}")
            state.w = state.w - 0.1 * grad
            state.step += 1
            if state.step % 2 == 0:
                state.commit()
        return np.asarray(state.w)

    w = train(state)
    print(f"ELASTIC_OK rank={rank} w={w.round(6).tolist()}")


def scenario_np8(hvd):
    """np=8 scale-out of the fusion/failure semantics (the richest
    behaviors had only ever run at np<=3): a 24-op fusion storm, two
    OVERLAPPING process sets with concurrent in-flight ops on both
    coordinators, a withdraw RACE (four ranks abandon the same op
    simultaneously), and a stall warning naming the THREE missing ranks
    — the reference ran its whole suite under real ``mpirun -np 2``
    (.travis.yml:96-103); this is that leg at 4x the scale."""
    import jax.numpy as jnp

    rank, size = hvd.rank(), hvd.size()
    assert size == 8, size

    # Leg 1 — fusion storm: 24 async allreduces in flight at once from
    # every rank.  Values are per-op distinct so a fused-buffer
    # misroute (wrong offsets) cannot cancel out.
    hs = [hvd.allreduce_async(jnp.full((8,), float(rank + 1) * (i + 1)),
                              average=False, name=f"storm.{i}")
          for i in range(24)]
    for i, h in enumerate(hs):  # sum_r (r+1)(i+1) = 36(i+1)
        np.testing.assert_allclose(np.asarray(hvd.synchronize(h)),
                                   36.0 * (i + 1))

    # Leg 2 — OVERLAPPING process sets {0..4} and {3..7}: ranks 3 and 4
    # are members of both and keep ops in flight on both per-set
    # coordinators at once.
    psa = hvd.add_process_set([0, 1, 2, 3, 4])
    psb = hvd.add_process_set([3, 4, 5, 6, 7])
    ha = hb = None
    if psa.included():
        ha = hvd.allreduce_async(jnp.full((2,), float(rank + 1)),
                                 average=False, process_set=psa,
                                 name="ov.a")
    if psb.included():
        hb = hvd.allreduce_async(jnp.full((2,), float(rank + 1)),
                                 average=False, process_set=psb,
                                 name="ov.b")
    if ha is not None:  # ranks 0..4 contribute 1+2+3+4+5
        np.testing.assert_allclose(np.asarray(hvd.synchronize(ha)), 15.0)
    if hb is not None:  # ranks 3..7 contribute 4+5+6+7+8
        np.testing.assert_allclose(np.asarray(hvd.synchronize(hb)), 30.0)
    # The global set still negotiates cleanly across all 8 afterwards.
    out = hvd.allreduce(jnp.ones((2,)), average=False, name="ov.world")
    np.testing.assert_allclose(np.asarray(out), 8.0)

    # Leg 3 — withdraw RACE: ranks 0-3 give up on the SAME never-ready
    # op at the same moment (four concurrent WITHDRAW frames, one of
    # them in-process on the controller); every withdrawer gets the
    # coordinator's group-wide abandonment error, and the group
    # survives.
    t0 = time.monotonic()
    if rank < 4:
        h = hvd.allreduce_async(jnp.ones((2,)), name="raced.op",
                                average=False)
        # who=None: four ranks race to withdraw; the named winner is
        # nondeterministic.
        _sync_expect_abandoned(hvd, h, None, t0, budget=30.0)
    else:
        time.sleep(5.0)  # outlive the racers' timeouts; never submit
    out = hvd.allreduce(jnp.ones((2,)), name="race.recover",
                        average=False)
    np.testing.assert_allclose(np.asarray(out), 8.0)

    # Leg 4 — stall warning naming THREE late ranks: 5, 6 and 7 sit out
    # past the threshold; the controller's stall report must list them
    # all (the np=2 leg only ever named one).
    threshold = float(os.environ["HOROVOD_STALL_WARNING_SECONDS"])
    if rank < 5:
        h = hvd.allreduce_async(jnp.ones((2,)), name="late8.op",
                                average=False)
        out = hvd.synchronize(h)
    else:
        time.sleep(3.0 * threshold)
        out = hvd.allreduce(jnp.ones((2,)), name="late8.op",
                            average=False)
    np.testing.assert_allclose(np.asarray(out), 8.0)
    print(f"NP8_OK rank={rank}")


def scenario_elastic2(hvd):
    """Elastic surviving TWO sequential hard deaths: rank 1 dies at step
    3 (incarnation 1) and again at step 7 (incarnation 2); each relaunch
    resumes from the last commit and the final weights must match an
    uninterrupted run, replayed in numpy in-process (both ranks' data
    streams are deterministic functions of the rank seed, so every rank
    can replay the whole job)."""
    import jax.numpy as jnp

    from horovod_tpu import elastic

    rank = hvd.rank()
    edir = os.environ["HVD_TPU_ELASTIC_DIR"]
    markers = [os.path.join(edir, "victim_died_1"),
               os.path.join(edir, "victim_died_2")]
    deaths = {3: markers[0], 7: markers[1]}
    total = 10

    w_true = np.array([1.0, -2.0], dtype="float32")
    data = []
    for r in range(2):
        rng = np.random.RandomState(23 + r)
        X = rng.normal(size=(total, 16, 2)).astype("float32")
        data.append((X, X @ w_true))
    X, y = data[rank]

    state = elastic.State(w=jnp.zeros((2,)), step=0)

    @elastic.run
    def train(state):
        if state.step > 0:
            print(f"ELASTIC2_RESUMED rank={rank} step={state.step}")
        while state.step < total:
            i = state.step
            marker = deaths.get(i)
            if rank == 1 and marker and not os.path.exists(marker):
                open(marker, "w").close()
                os._exit(1)  # hard failure, no handshake
            xb, yb = jnp.asarray(X[i]), jnp.asarray(y[i])
            grad = 2.0 * xb.T @ (xb @ state.w - yb) / xb.shape[0]
            grad = hvd.allreduce(grad, average=True, name=f"el2.grad.{i}")
            state.w = state.w - 0.1 * grad
            state.step += 1
            if state.step % 2 == 0:
                state.commit()
        return np.asarray(state.w)

    w = train(state)
    # In-process replay of the uninterrupted arithmetic (f32 like the
    # training loop).
    want = np.zeros(2, dtype="float32")
    for i in range(total):
        grads = [2.0 * Xr[i].T @ (Xr[i] @ want - yr[i]) / Xr[i].shape[0]
                 for Xr, yr in data]
        want = want - 0.1 * (grads[0] + grads[1]) / 2.0
    np.testing.assert_allclose(w, want, atol=1e-4)
    print(f"ELASTIC2_OK rank={rank}")


def scenario_verify(hvd):
    """verify_program across REAL processes (hvd-analyze pass 1): the
    matching program verifies clean over the TCP control plane, then
    every divergence kind — dtype, shape, order, count, and the
    process-set wait-for CYCLE no runtime check can catch — fails at
    verify time with a diagnostic naming the first divergent entry and
    both ranks' records.  All cases run in ONE launch, and — true to
    "verify BEFORE the data plane" — no collective is ever synchronized:
    the divergent ops are enqueued async only, so every negotiation
    either errors or stays pending (poisoned at shutdown) and the group
    stays healthy between cases; verify_program's reset isolates each
    round."""
    import jax.numpy as jnp

    from horovod_tpu import HorovodError, verify_program
    from horovod_tpu.analysis import program as _prog

    rank = hvd.rank()

    # Round 0 — identical signatures verify clean.  The roots diverge,
    # but root_rank is deliberately OUTSIDE the signature (the runtime
    # validator owns it): this also pins the verifier's scope.
    _prog.recorder().clear()
    hvd.broadcast_async(jnp.ones((2,)), root_rank=rank, name="v.same")
    rep = verify_program()
    assert rep.ranks == 2 and rep.entries == 1, rep
    print(f"VERIFY_OK rank={rank}")

    def expect(case: str, want: str, both_records: bool = True):
        try:
            verify_program()
            raise AssertionError(f"case {case}: expected divergence")
        except HorovodError as e:
            assert want in str(e), (case, str(e))
            if both_records:
                assert "rank 0" in str(e) and "rank 1" in str(e), str(e)
        print(f"VERIFY_DIVERGE_OK rank={rank} case={case}")

    # dtype: same name, one rank traced float32, the other int32.
    hvd.allreduce_async(jnp.ones(
        (2,), jnp.float32 if rank == 0 else jnp.int32),
        average=False, name="v.dtype")
    expect("dtype", "Mismatched data types")

    # shape: same name, rank-dependent shape.
    hvd.allreduce_async(jnp.ones((2 + rank,)), average=False,
                        name="v.shape")
    expect("shape", "Mismatched tensor shapes")

    # order: the two ranks enqueue the same two ops swapped — the
    # name-keyed coordinator would stall on this forever.  (The dtype
    # rides the rank so the swapped negotiations error out instead of
    # completing into data-plane work this scenario never wants.)
    dt = jnp.float32 if rank == 0 else jnp.int32
    for n in (["v.a", "v.b"] if rank == 0 else ["v.b", "v.a"]):
        hvd.allreduce_async(jnp.ones((2,), dt), average=False, name=n)
    expect("order", "Mismatched tensor names")

    # count: rank 1 traced one collective more than rank 0 (the common
    # entry is signature-identical — divergent root only — so the
    # count check, not a field diff, is what fires).
    hvd.broadcast_async(jnp.ones((2,)), root_rank=rank, name="v.c0")
    if rank == 1:
        hvd.allreduce_async(jnp.ones((2,)), average=False, name="v.c1")
    expect("count", "Rank-divergent collective count",
           both_records=False)

    # process-set cycle: rank 0 traces set-1-then-set-2, rank 1 the
    # swap.  Each set's coordinator would see a perfectly consistent
    # stream, so only the wait-for-graph check can catch the deadlock
    # synchronous callers would hit.  Recorded through the public
    # capture hook so the cycle stands alone in the signature
    # (registering real sets would prepend its own collective rounds).
    _prog.recorder().clear()
    order = [("v.x", 1), ("v.y", 2)] if rank == 0 \
        else [("v.y", 2), ("v.x", 1)]
    for n, psid in order:
        _prog.record_collective("allreduce", n, "float32", (2,),
                                reduce_op="sum", process_set_id=psid)
    expect("cycle", "Potential process-set deadlock cycle")
    print(f"VERIFY_ALL_OK rank={rank}")


def scenario_cache(hvd):
    """Response-cache steady state + every invalidation hook across REAL
    processes (ops/cache.py): after the first negotiation of a repeated
    named program, workers ship one coalesced bit-vector frame per tick
    and rank 0 replays cached responses (skipping submit/
    construct_response); a mid-run program change, hvd.join(), process-
    set add/remove and an autotune threshold update each flush the
    cache with a logged marker while results stay exactly correct.
    Runs identically with HVD_TPU_RESPONSE_CACHE=0 (minus the stats
    asserts) — the numerical-identity leg of the acceptance criteria."""
    import jax.numpy as jnp

    from horovod_tpu import HorovodError
    from horovod_tpu.core import state as _st

    rank = hvd.rank()
    st = _st.global_state()
    cache = st.response_cache
    cache_on = os.environ.get("HVD_TPU_RESPONSE_CACHE", "1") != "0"
    assert (cache is not None) == cache_on, (cache, cache_on)

    # Leg 1 — steady state: the identical named program for 4 steps.
    # Values are rank- and step-dependent so a replayed response feeding
    # the wrong op (or a stale cached result) cannot produce them.
    for step in range(4):
        for i in range(3):
            out = hvd.allreduce(
                jnp.full((4,), float(rank + 1) * (i + 1)),
                average=False, name=f"c.grad.{i}")
            np.testing.assert_allclose(np.asarray(out), 3.0 * (i + 1))
        g = np.asarray(hvd.allgather(
            jnp.full((rank + 1, 2), float(rank)), name="c.gather"))
        assert g.shape == (3, 2), g.shape
        np.testing.assert_allclose(g[:1], 0.0)
        np.testing.assert_allclose(g[1:], 1.0)
        b = np.asarray(hvd.broadcast(jnp.full((2,), float(rank)), 1,
                                     name="c.bcast"))
        np.testing.assert_allclose(b, 1.0)
    hits = 0
    if cache_on:
        s = cache.stats
        hits = s.hits
        assert s.hits > 0, s  # every rank's replica must be serving
        if rank == 0:
            assert s.replayed_tensors > 0, s
    print(f"CACHE_STEADY_OK rank={rank} hits={hits}")

    # Leg 2 — program change mid-run: the same name returns with a new
    # (rank-divergent) shape.  The cached cycle must flush (logged) and
    # the standard cross-rank mismatch diagnosis must fire — not a
    # stale replay of the old shape.
    try:
        hvd.allreduce(jnp.ones((2 + rank,)), average=False,
                      name="c.grad.0")
        raise AssertionError("changed program did not raise")
    except HorovodError as e:
        assert "Mismatched allreduce tensor shapes" in str(e), str(e)
    out = hvd.allreduce(jnp.ones((2,)), average=False, name="c.recover")
    np.testing.assert_allclose(np.asarray(out), 2.0)
    print(f"CACHE_CHANGE_OK rank={rank}")

    # Leg 3 — hvd.join(): rank 0 runs out after 2 steps; negotiations
    # completed via the join must not poison the cache (insertion is
    # disarmed until the release), and results stay exact.
    steps = 2 if rank == 0 else 4
    for i in range(steps):
        out = hvd.allreduce(jnp.full((3,), float(rank + 1)),
                            average=False, name=f"c.join.{i}")
        want = 3.0 if i < 2 else 2.0  # rank 0 joined: zeros + rank 1
        np.testing.assert_allclose(np.asarray(out), want)
    assert hvd.join() == 1
    out = hvd.allreduce(jnp.ones((2,)), average=False, name="c.post.join")
    np.testing.assert_allclose(np.asarray(out), 2.0)
    print(f"CACHE_JOIN_OK rank={rank}")

    # Leg 4 — process-set add/remove: both flush every replica at the
    # registration allgather's stream position; set collectives and the
    # global set keep working before, between and after.
    ps = hvd.add_process_set([0, 1])
    out = hvd.allreduce(jnp.full((2,), float(rank + 1)), average=False,
                        process_set=ps, name="c.ps")
    np.testing.assert_allclose(np.asarray(out), 3.0)
    assert hvd.remove_process_set(ps)
    out = hvd.allreduce(jnp.ones((2,)), average=False, name="c.ps.after")
    np.testing.assert_allclose(np.asarray(out), 2.0)
    print(f"CACHE_PSETS_OK rank={rank}")

    # Leg 5 — autotune fusion-threshold update: entries survive, the
    # memoized packing plans flush (logged on the coordinator).
    for _ in range(2):  # second pass replays → builds a cached plan
        out = hvd.allreduce(jnp.ones((2,)), average=False, name="c.tune")
        np.testing.assert_allclose(np.asarray(out), 2.0)
    if rank == 0 and st.coordinator is not None:
        st.coordinator.set_fusion_threshold(1 << 20)
    out = hvd.allreduce(jnp.ones((2,)), average=False, name="c.tune")
    np.testing.assert_allclose(np.asarray(out), 2.0)
    print(f"CACHE_TUNE_OK rank={rank}")

    if cache_on:
        s = cache.stats
        assert s.flushes > 0, s
        print(f"CACHE_OK rank={rank} hits={s.hits} flushes={s.flushes}")
    else:
        print(f"CACHE_OK rank={rank} hits=0 flushes=0")


def scenario_metrics(hvd):
    """hvd-telemetry cluster aggregation over the REAL control plane:
    both ranks seed negotiation traffic, then rank 0 pulls every
    rank's snapshot over FRAME_METRICS and asserts the fleet aggregate
    covers all ranks (rank 1 answers from its receive thread while
    blocked in its own barrier).

    The seeding uses deliberately MISMATCHED shapes: the full control
    plane runs — per-rank submits, coalesced frames, rank-0
    validation, ERROR broadcast — with zero data-plane execution, so
    this leg (unlike the np>1 XLA-collective legs) also verifies under
    jax builds whose CPU backend cannot run multiprocess
    computations."""
    import jax.numpy as jnp

    from horovod_tpu import HorovodError

    rank = hvd.rank()

    def control_plane_round(name):
        try:
            hvd.allreduce(jnp.zeros((2 + rank,), jnp.float32), name=name,
                          average=False)
            raise AssertionError(f"mismatched {name} did not raise")
        except HorovodError as e:
            assert "Mismatched allreduce tensor shapes" in str(e), str(e)

    for i in range(3):
        control_plane_round(f"met.{i}")

    local = hvd.metrics()
    assert local["collective.submitted"]["value"] >= 3, local
    assert local["collective.errors"]["value"] >= 3, local
    assert local["collective.negotiate_seconds"]["count"] >= 3, local
    assert local["transport.frames_sent"]["value"] >= 1, local

    if rank == 0:
        agg = hvd.cluster_metrics(timeout=30.0)
        m = agg["collective.submitted"]
        assert m["ranks"] == hvd.size(), m
        assert m["min"] >= 3, m
        assert agg["collective.errors"]["sum"] >= 3 * hvd.size(), agg
        h = agg["collective.negotiate_seconds"]
        assert h["count"] >= 3 * hvd.size(), h
        assert h["p50"] is not None and h["p99"] is not None, h
        assert agg["transport.frames_sent"]["sum"] >= 2, agg
    else:
        try:
            hvd.cluster_metrics(timeout=1.0)
            raise AssertionError("cluster_metrics must be rank-0-only")
        except RuntimeError as e:
            assert "rank-0" in str(e), str(e)
    # Barrier keeps rank 1 alive (and answering pulls) until rank 0's
    # aggregation finished — the mismatch completes negotiation on both
    # ranks, so it synchronizes without touching the data plane.
    control_plane_round("met.done")
    print(f"METRICS_OK rank={rank}")


def scenario_trace(hvd):
    """hvd-trace acceptance (ISSUE 10): a seeded slow rank (rank 1
    pays a loader stall before each collective — the slow-loader
    scenario, instrumented exactly as the prefetch consumer
    instruments its blocked wait) across REAL processes.  Rank 0 then
    (a) merges the fleet trace — both ranks present, same-(step,
    cycle) negotiate spans OVERLAP after clock correction — and (b)
    runs the analyzer, which must attribute the stall to rank 1 with
    blame category ``host``.

    Control-plane-only traffic (the scenario_metrics trick:
    deliberately mismatched shapes negotiate fully, broadcast an ERROR
    and execute it on every rank with zero data-plane work), so this
    leg runs under any jax build."""
    import json as _json
    import time as _time

    import jax.numpy as jnp

    import horovod_tpu.trace as trace
    from horovod_tpu import HorovodError

    rank = hvd.rank()
    out = os.environ.get("HVD_TPU_TRACE_OUT",
                         "/tmp/hvd_fleet_trace.json")
    for step in range(1, 4):
        trace.set_step(step)
        if rank == 1:
            # The slow loader: a real stall on this rank's step path,
            # recorded as the host-leg span prefetch_to_device records
            # for its blocked consumer.
            t0 = _time.monotonic()
            _time.sleep(0.15)
            trace.span("prefetch.wait", "host", t0, _time.monotonic())
        try:
            hvd.allreduce(jnp.zeros((2 + rank,), jnp.float32),
                          name=f"tr.{step}", average=False)
            raise AssertionError("mismatched tr did not raise")
        except HorovodError as e:
            assert "Mismatched allreduce tensor shapes" in str(e), \
                str(e)
    _time.sleep(0.3)  # let the last broadcast's spans land everywhere

    if rank == 0:
        path = hvd.dump_fleet_trace(out, timeout=30.0)
        data = _json.load(open(path))
        evs = [e for e in data["traceEvents"] if e.get("ph") == "X"]
        pids = {e["pid"] for e in evs}
        assert {0, 1} <= pids, pids
        # Clock alignment ran: a measured offset for the worker.
        assert "1" in data["metadata"]["clock_offsets_seconds"], \
            data["metadata"]
        # Same-(step, cycle) negotiate spans from BOTH ranks overlap
        # after clock correction — every rank's submit->execute window
        # contains the shared [last submit, broadcast] interval.
        windows = {}
        for e in evs:
            if e["cat"] != "negotiate":
                continue
            k = (e["args"]["step"], e["args"]["cycle"])
            lo, hi = e["ts"], e["ts"] + e["dur"]
            cur = windows.setdefault(k, {}).get(e["pid"])
            windows[k][e["pid"]] = (
                (lo, hi) if cur is None
                else (min(cur[0], lo), max(cur[1], hi)))
        shared = [k for k, d in windows.items() if {0, 1} <= set(d)]
        assert shared, windows
        overlaps = [k for k in shared
                    if windows[k][0][0] < windows[k][1][1]
                    and windows[k][1][0] < windows[k][0][1]]
        assert overlaps, (shared, windows)
        # The analyzer names the seeded slow rank with blame "host".
        from horovod_tpu.trace.analyze import analyze

        report = analyze(data["traceEvents"])
        host_blamed = [c for c in report["cycles"]
                       if c["straggler"] == 1 and c["blame"] == "host"]
        assert len(host_blamed) >= 3, report["cycles"]
        # Determinism (the CI trace-analysis gate): two replays of the
        # same merged file are byte-identical.
        a = _json.dumps(analyze(data["traceEvents"]), sort_keys=True)
        b = _json.dumps(analyze(data["traceEvents"]), sort_keys=True)
        assert a == b
    else:
        try:
            hvd.dump_fleet_trace(out)
            raise AssertionError("dump_fleet_trace must be rank-0-only")
        except RuntimeError as e:
            assert "rank-0" in str(e), str(e)
    # Barrier via a full-negotiation mismatch: keeps rank 1 alive (and
    # answering the FRAME_TRACE pull) until rank 0's merge finished.
    try:
        hvd.allreduce(jnp.zeros((2 + rank,), jnp.float32),
                      name="tr.done", average=False)
        raise AssertionError("mismatched tr.done did not raise")
    except HorovodError:
        pass
    print(f"TRACE_OK rank={rank}")


def scenario_combo(hvd):
    """Run several NON-DESTRUCTIVE scenarios sequentially in ONE launch
    (``HVD_TPU_COMBO`` names them, comma-separated).  Every separate
    launch pays full JAX init on every rank on the 1-core CI box, so
    batching the scenarios that leave the group healthy — collectives,
    mismatch validation, SPMD training, withdrawal recovery, stall
    recovery, checkpoint, torch/tf frontends — cuts the suite's
    wall-clock by minutes without losing any coverage: each scenario
    still prints its own marker for the test to assert."""
    for name in os.environ["HVD_TPU_COMBO"].split(","):
        globals()[f"scenario_{name}"](hvd)
    print(f"COMBO_OK rank={hvd.rank()}")


def main():
    scenario = sys.argv[1]
    import horovod_tpu as hvd

    hvd.init()
    try:
        globals()[f"scenario_{scenario}"](hvd)
    finally:
        if not getattr(main, "skip_shutdown", False):
            hvd.shutdown()


if __name__ == "__main__":
    main()
