"""REAL multi-process integration tests: N worker processes launched by
the runner, speaking through the actual JAX coordination service
(KVTransport) and the cross-process XLA CPU data plane (gloo-backed
collectives).

This is the analog of the reference's ``test/parallel/*`` suite running
under ``horovodrun -np N`` on localhost (SURVEY.md §4 patterns 1-2):
test bodies are SPMD — every rank runs the same function — and the
launcher is the real one, not a mock.  Each test bundles many asserts
into one launch because process spawn + rendezvous costs seconds.
"""

import os

import pytest

import horovod_tpu
from horovod_tpu.runner import RunError, run

pytestmark = pytest.mark.multiprocess

_REPO_ROOT = os.path.dirname(os.path.dirname(horovod_tpu.__file__))
_ENV = {"PYTHONPATH": _REPO_ROOT + os.pathsep
        + os.environ.get("PYTHONPATH", "")}


def _run(body, np=2, cpu_devices=1, **kw):
    return run(body, np=np, cpu_devices=cpu_devices, env=_ENV,
               start_timeout=300.0, **kw)


def test_sync_collectives_2proc():
    """The full sync eager op matrix across 2 real processes."""

    def body():
        import jax
        import jax.numpy as jnp
        import numpy as np

        import horovod_tpu as hvt

        hvt.init()
        r, s = hvt.rank(), hvt.size()
        assert s == 2
        out = {}

        # allreduce (sum + average + prescale)
        x = jnp.full((3,), float(r + 1))
        out["sum"] = np.asarray(hvt.allreduce(x, op=hvt.Sum)).tolist()
        out["avg"] = np.asarray(hvt.allreduce(x, op=hvt.Average)).tolist()
        out["pre"] = np.asarray(
            hvt.allreduce(x, op=hvt.Sum, prescale_factor=2.0)
        ).tolist()

        # ragged allgather: rank r contributes r+1 rows of value r
        g = hvt.allgather(jnp.full((r + 1, 2), float(r)))
        out["gather"] = np.asarray(g).tolist()

        # broadcast from rank 1
        b = hvt.broadcast(jnp.full((2,), float(r * 10)), root_rank=1)
        out["bcast"] = np.asarray(b).tolist()

        # alltoall with variable splits: rank 0 sends [1 row, 2 rows],
        # rank 1 sends [3 rows, 1 row]
        splits = [1, 2] if r == 0 else [3, 1]
        t = jnp.arange(sum(splits), dtype=jnp.float32) + 100 * r
        recv, rsplits = hvt.alltoall(t, splits=splits)
        out["a2a"] = np.asarray(recv).tolist()
        out["a2a_splits"] = np.asarray(rsplits).tolist()

        # reducescatter, uneven dim0 (5 rows over 2 ranks -> 3/2)
        rs = hvt.reducescatter(jnp.ones((5, 2)), op=hvt.Sum)
        out["rs_shape"] = list(rs.shape)

        # barrier
        hvt.barrier()
        return (r, out)

    results = _run(body, np=2)
    for r, out in results:
        assert out["sum"] == [3.0, 3.0, 3.0]
        assert out["avg"] == [1.5, 1.5, 1.5]
        assert out["pre"] == [6.0, 6.0, 6.0]
        assert out["gather"] == [[0.0, 0.0], [1.0, 1.0], [1.0, 1.0]]
        assert out["bcast"] == [10.0, 10.0]
        # rank 0 receives: its own first chunk [100*0+0], rank 1's first
        # chunk (3 rows). rank 1 receives rank 0's second chunk (2 rows)
        # + its own second chunk (1 row).
        if r == 0:
            assert out["a2a"] == [0.0, 100.0, 101.0, 102.0]
            assert out["a2a_splits"] == [1, 3]
        else:
            assert out["a2a"] == [1.0, 2.0, 103.0]
            assert out["a2a_splits"] == [2, 1]
        assert out["rs_shape"] == ([3, 2] if r == 0 else [2, 2])


def test_async_controller_negotiation_2proc():
    """Ranks enqueue async ops in DIFFERENT orders; the controller must
    negotiate one execution order through the real KVTransport (the
    core Horovod property — never before exercised across processes)."""

    def body():
        import jax.numpy as jnp
        import numpy as np

        import horovod_tpu as hvt

        hvt.init()
        r = hvt.rank()
        names = ["a", "b", "c", "d"] if r == 0 else ["d", "c", "b", "a"]
        handles = {
            n: hvt.allreduce_async(
                jnp.full((8,), float((r + 1) * (i + 1))), name=n,
                op=hvt.Sum,
            )
            for i, n in enumerate(names)
        }
        vals = {n: float(np.asarray(hvt.synchronize(h))[0])
                for n, h in handles.items()}

        # grouped allreduce: members only execute together
        g = hvt.grouped_allreduce_async(
            [jnp.full((2,), float(r)), jnp.full((3,), float(r + 1))],
            names=["g1", "g2"], op=hvt.Sum,
        )
        grouped = [np.asarray(hvt.synchronize(h)).tolist() for h in g]

        # async broadcast + ragged allgather through the controller
        hb = hvt.broadcast_async(jnp.full((2,), float(r)), root_rank=0,
                                 name="bc")
        hg = hvt.allgather_async(jnp.full((r + 2,), 1.0), name="ag")
        bcast = np.asarray(hvt.synchronize(hb)).tolist()
        gath = np.asarray(hvt.synchronize(hg)).tolist()
        return (r, vals, grouped, bcast, gath)

    results = _run(body, np=2)
    for r, vals, grouped, bcast, gath in results:
        # rank0 enqueued (i+1), rank1 enqueued 2(i+1) with names reversed:
        # a: r0 gives 1, r1 gives 2*4=8 -> 9 ... pair by NAME not order.
        assert vals == {"a": 1.0 + 8.0, "b": 2.0 + 6.0,
                        "c": 3.0 + 4.0, "d": 4.0 + 2.0}
        assert grouped == [[1.0, 1.0], [3.0, 3.0, 3.0]]
        assert bcast == [0.0, 0.0]
        assert gath == [1.0] * 5


def test_process_sets_and_fusion_4proc():
    """Process-set-scoped collectives + fused small tensors, 4 procs."""

    def body():
        import jax.numpy as jnp
        import numpy as np

        import horovod_tpu as hvt

        hvt.init()
        r, s = hvt.rank(), hvt.size()
        assert s == 4
        evens = hvt.add_process_set([0, 2])
        odds = hvt.add_process_set([1, 3])
        mine = evens if r % 2 == 0 else odds

        # sync collective scoped to the set
        v = float(np.asarray(
            hvt.allreduce(jnp.asarray([float(r)]), op=hvt.Sum,
                          process_set=mine)
        )[0])

        # async: many small tensors -> the controller fuses them into
        # one flat wire buffer per cycle (FusionBufferManager parity)
        handles = [
            hvt.allreduce_async(jnp.full((4,), float(r + i)),
                                name=f"t{i}", op=hvt.Sum)
            for i in range(6)
        ]
        fused = [float(np.asarray(hvt.synchronize(h))[0]) for h in handles]

        # set-scoped async allgather
        hg = hvt.allgather_async(jnp.asarray([float(r)]), name="ps_ag",
                                 process_set=mine)
        ps_gather = np.asarray(hvt.synchronize(hg)).tolist()
        return (r, v, fused, ps_gather)

    results = _run(body, np=4)
    for r, v, fused, ps_gather in results:
        expected_set = 0.0 + 2.0 if r % 2 == 0 else 1.0 + 3.0
        assert v == expected_set
        assert fused == [float(sum(rr + i for rr in range(4)))
                         for i in range(6)]
        assert ps_gather == ([0.0, 2.0] if r % 2 == 0 else [1.0, 3.0])


def test_torch_bare_collective_gradients_2proc():
    """autograd through BARE torch collectives across ranks (parity:
    the torch.autograd.Function registrations): grad of an averaged
    allreduce averages the rank-local upstream grads; allgather's
    grad sums-and-slices; broadcast's reduces to the root."""

    def body():
        import torch

        import horovod_tpu.torch as hvd

        hvd.init()
        r = hvd.rank()
        out = {}

        # replicated weight through a bare averaged allreduce with a
        # rank-local coefficient: grad = avg over ranks of the coeff
        w = torch.tensor([[2.0]], requires_grad=True)
        c = float(10 * (r + 1))
        (hvd.allreduce(w, op=hvd.Average) * c).sum().backward()
        out["bare"] = w.grad.ravel().tolist()

        # allgather grad: summed coeffs, sliced to this rank's rows
        x = torch.ones((r + 1, 2), requires_grad=True)
        coeff = torch.tensor([[1.0], [2.0], [3.0]])
        (hvd.allgather(x) * coeff).sum().backward()
        out["gather_grad"] = x.grad.tolist()

        # broadcast grad: reduce-to-root
        b = torch.tensor([float(r + 5)], requires_grad=True)
        (hvd.broadcast(b, root_rank=0) * float(r + 1)).sum().backward()
        out["bcast_grad"] = b.grad.tolist()

        # no-splits alltoall with DIFFERENT per-rank row counts: the
        # adjoint must route each grad row back via the RECEIVED
        # counts (rank0 sends 2 rows to each peer, rank1 sends 1)
        t = torch.arange(float((2 - r) * 2), requires_grad=True)
        recv = hvd.alltoall(t)
        (recv * float(r + 1)).sum().backward()
        out["a2a_grad"] = t.grad.tolist()
        return (r, out)

    results = _run(body, np=2)
    for r, out in results:
        assert out["bare"] == [15.0]  # avg(10, 20)
        if r == 0:
            assert out["gather_grad"] == [[2.0, 2.0]]
        else:
            assert out["gather_grad"] == [[4.0, 4.0], [6.0, 6.0]]
        assert out["bcast_grad"] == ([3.0] if r == 0 else [0.0])
        # rank0's rows 0-1 were received by rank0 (coeff 1), rows 2-3
        # by rank1 (coeff 2); rank1's row 0 by rank0, row 1 by rank1
        if r == 0:
            assert out["a2a_grad"] == [1.0, 1.0, 2.0, 2.0]
        else:
            assert out["a2a_grad"] == [1.0, 2.0]


def test_torch_optimizer_2proc():
    """The torch frontend end-to-end across processes: broadcast
    parameters, DistributedOptimizer averaging gradients."""

    def body():
        import numpy as np
        import torch

        import horovod_tpu.torch as hvd

        hvd.init()
        r = hvd.rank()
        torch.manual_seed(1234 + r)  # different init per rank
        model = torch.nn.Linear(4, 2)
        hvd.broadcast_parameters(model.state_dict(), root_rank=0)
        w0 = model.weight.detach().clone().numpy()

        opt = torch.optim.SGD(model.parameters(), lr=0.1)
        opt = hvd.DistributedOptimizer(
            opt, named_parameters=model.named_parameters()
        )
        torch.manual_seed(r)  # different data per rank
        x = torch.randn(8, 4)
        y = torch.randn(8, 2)
        for _ in range(2):
            opt.zero_grad()
            loss = torch.nn.functional.mse_loss(model(x), y)
            loss.backward()
            opt.step()
        return (r, w0.tolist(), model.weight.detach().numpy().tolist())

    results = _run(body, np=2)
    (r0, w0_init, w0_final), (r1, w1_init, w1_final) = results
    # broadcast made initial params identical; averaged grads keep them
    # identical through steps despite different per-rank data
    assert w0_init == w1_init
    assert w0_final == w1_final
    assert w0_final != w0_init  # training moved


def test_grouped_variants_and_compression_2proc():
    """Grouped allgather/reducescatter across real processes + fp16
    wire compression on the async allreduce path."""

    def body():
        import jax.numpy as jnp
        import numpy as np

        import horovod_tpu as hvt
        from horovod_tpu.comm.compression import Compression

        hvt.init()
        r = hvt.rank()
        out = {}

        hs = hvt.grouped_allgather_async(
            [jnp.full((r + 1, 2), float(r)), jnp.asarray([float(r)])],
            names=["g1", "g2"],
        )
        g1, g2 = [np.asarray(hvt.synchronize(h)) for h in hs]
        out["g1"] = g1.tolist()
        out["g2"] = g2.tolist()

        hs = hvt.grouped_reducescatter_async(
            [jnp.ones((4, 2)), jnp.full((2,), float(r + 1))],
            names=["r1", "r2"], op=hvt.Sum,
        )
        r1, r2 = [np.asarray(hvt.synchronize(h)) for h in hs]
        out["r1_shape"] = list(r1.shape)
        out["r2"] = r2.tolist()

        h = hvt.allreduce_async(
            jnp.full((8,), 1.5 + r), name="fp16c", op=hvt.Sum,
            compression=Compression.fp16,
        )
        out["fp16"] = float(np.asarray(hvt.synchronize(h))[0])

        # int8 (incl. the stochastic subclass) must stay OFF the fused
        # flat-buffer path: per-rank block scales don't survive a raw
        # summed wire (regression: the controller's unfusable check
        # matched Int8Compressor by identity, so the subclass fused and
        # produced garbage).  Two concurrent ops makes the controller
        # emit one fused response covering both.
        hs = [
            hvt.allreduce_async(
                jnp.full((16,), 2.0 + r), name="q8a", op=hvt.Sum,
                compression=Compression.int8_stochastic,
            ),
            hvt.allreduce_async(
                jnp.full((16,), 10.0 * (r + 1)), name="q8b", op=hvt.Sum,
                compression=Compression.int8_stochastic,
            ),
        ]
        q8a, q8b = [np.asarray(hvt.synchronize(h)) for h in hs]
        out["q8a"] = float(q8a[0])
        out["q8b"] = float(q8b[0])
        return (r, out)

    results = _run(body, np=2)
    for r, out in results:
        assert out["g1"] == [[0.0, 0.0], [1.0, 1.0], [1.0, 1.0]]
        assert out["g2"] == [0.0, 1.0]
        assert out["r1_shape"] == [2, 2]
        # reducescatter of (2,) over 2 ranks -> 1 element per rank
        assert out["r2"] == [3.0]
        assert out["fp16"] == 4.0  # 1.5 + 2.5, exact in fp16
        # 2+3=5 and 10+20=30, within one int8 quantization step
        assert abs(out["q8a"] - 5.0) <= 5.0 / 127 + 1e-6
        assert abs(out["q8b"] - 30.0) <= 30.0 / 127 + 1e-6


def test_join_uneven_batches_2proc():
    """JoinOp semantics across real processes: rank 1 exhausts its data
    after 1 batch and joins; rank 0 runs 2 more batches whose
    allreduces must complete with rank 1 contributing zeros (sum keeps
    only rank 0's grads; average still divides by world size —
    reference join semantics)."""

    def body():
        import jax.numpy as jnp
        import numpy as np

        import horovod_tpu as hvt

        hvt.init()
        r = hvt.rank()
        out = {}
        # batch 0: everyone participates
        h = hvt.allreduce_async(jnp.full((4,), float(r + 1)), name="b0",
                                op=hvt.Sum)
        out["b0"] = float(np.asarray(hvt.synchronize(h))[0])
        if r == 1:
            last = hvt.join()  # out of data
            out["join_last"] = last
        else:
            # two uneven extra batches
            h1 = hvt.allreduce_async(jnp.full((4,), 10.0), name="b1",
                                     op=hvt.Sum)
            out["b1"] = float(np.asarray(hvt.synchronize(h1))[0])
            h2 = hvt.allreduce_async(jnp.full((4,), 8.0), name="b2",
                                     op=hvt.Average)
            out["b2"] = float(np.asarray(hvt.synchronize(h2))[0])
            out["join_last"] = hvt.join()
        return (r, out)

    results = _run(body, np=2)
    for r, out in results:
        assert out["b0"] == 3.0
        # rank 1 joined first, rank 0 last -> join() returns 0 everywhere
        assert out["join_last"] == 0
        if r == 0:
            assert out["b1"] == 10.0  # rank 1 contributed zeros
            assert out["b2"] == 4.0   # (8 + 0) / 2: zeros count in avg


@pytest.mark.slow
def test_elastic_reset_callback_rebroadcast_2proc():
    """ADVICE r5 regression: a RANK-DEPENDENT reset callback in a
    relaunched incarnation runs after sync; without the wrapper's
    re-broadcast the tracked attributes silently diverge across
    ranks.  Both ranks must come out with rank 0's values."""

    def body():
        import os

        import horovod_tpu as hvt
        from horovod_tpu import elastic

        os.environ["HVTPU_ELASTIC_GENERATION"] = "1"
        hvt.init()
        state = elastic.ObjectState(lr=0.0, epoch=3)
        state.register_reset_callbacks(
            [lambda: setattr(state, "lr", 100.0 + hvt.rank())])

        @elastic.run
        def train(st):
            return (st.lr, st.epoch)

        return train(state)

    results = _run(body, np=2)
    assert results[0] == results[1] == (100.0, 3)


def test_hierarchical_allreduce_4proc():
    """HVTPU_HIERARCHICAL_ALLREDUCE over a 2-host x 2-slot layout
    (both 'hosts' are loopback names, so everything spawns locally but
    local/cross topology is real): the two-stage (ici then dcn) reduce
    must produce the same numbers as the flat path."""

    def body():
        import jax.numpy as jnp
        import numpy as np

        import horovod_tpu as hvt

        hvt.init()
        r = hvt.rank()
        assert hvt.local_size() == 2 and hvt.cross_size() == 2
        assert hvt.size() == 4
        s = np.asarray(hvt.allreduce(
            jnp.full((5,), float(r + 1)), op=hvt.Sum
        )).tolist()
        a = np.asarray(hvt.allreduce(
            jnp.full((3,), float(10 * (r + 1))), op=hvt.Average
        )).tolist()
        return (r, s, a)

    results = run(
        body, np=4, cpu_devices=1,
        hosts="localhost:2,127.0.0.1:2",
        env={**_ENV, "HVTPU_HIERARCHICAL_ALLREDUCE": "1"},
        start_timeout=300.0,
    )
    for r, s, a in results:
        assert s == [10.0] * 5          # 1+2+3+4
        assert a == [25.0] * 3          # avg(10,20,30,40)


def test_sparse_allreduce_2proc():
    """sparse_allreduce_async across real processes: overlapping and
    disjoint embedding rows from two ranks coalesce to the cross-rank
    sum (reference: entries+values allgather path)."""

    def body():
        import torch

        import horovod_tpu.torch as hvd

        hvd.init()
        r = hvd.rank()
        # rank 0 touches rows {0, 1}; rank 1 touches rows {1, 2}
        i = torch.tensor([[0 + r, 1 + r]])
        v = torch.tensor([[1.0 * (r + 1)], [10.0 * (r + 1)]])
        sp = torch.sparse_coo_tensor(i, v, size=(4, 1))
        out = hvd.synchronize(
            hvd.sparse_allreduce_async(sp, name="emb", op=hvd.Sum)
        )
        return (r, out.to_dense().squeeze(1).tolist())

    results = _run(body, np=2)
    for r, dense in results:
        # row0: rank0's 1.0; row1: rank0's 10.0 + rank1's 2.0; row2:
        # rank1's 20.0
        assert dense == [1.0, 12.0, 20.0, 0.0]


def test_early_exit_rank_does_not_hang_peers():
    """A worker that finishes and exits must not hang the remaining
    ranks' coordination: its shutdown farewell tells the coordinator to
    stop waiting for its cycle blobs (the controller cycle gathers from
    every rank otherwise)."""

    def body():
        import time

        import jax.numpy as jnp

        import horovod_tpu as hvt

        hvt.init()
        r = hvt.rank()
        solo = hvt.add_process_set([0])
        other = hvt.add_process_set([1])
        hvt.synchronize(
            hvt.allreduce_async(jnp.ones(2), name="warm", op=hvt.Sum)
        )
        if r == 1:
            return "bye"  # exits while rank 0 keeps coordinating
        mine = solo
        for i in range(20):
            hvt.synchronize(hvt.allreduce_async(
                jnp.ones(2), name=f"solo{i}", op=hvt.Sum,
                process_set=mine,
            ))
            time.sleep(0.05)
        return "done"

    results = _run(body, np=2)
    assert [x[1] if isinstance(x, tuple) else x for x in results] \
        == ["done", "bye"] or results == ["done", "bye"]


def test_worker_failure_propagates():
    """One rank raising must fail the job with that rank's traceback
    and terminate the peers (reference: launcher exit-code handling)."""

    def body():
        import horovod_tpu as hvt

        hvt.init()
        if hvt.rank() == 1:
            raise RuntimeError("deliberate-worker-crash")
        return hvt.rank()

    with pytest.raises(RunError) as err:
        _run(body, np=2)
    assert "deliberate-worker-crash" in str(err.value)


# ---------------------------------------------------------------------------
# Pod shape: P processes x D>1 local devices (the north star's topology —
# many hosts x several chips each, one jit program over the global mesh)
# ---------------------------------------------------------------------------


def _pod_train_body():
    """SPMD body: jit DistributedOptimizer training step over the GLOBAL
    8-device world mesh from each of 2 processes owning 4 devices
    (multi-controller JAX: same jit on every process, per-host
    addressable shards).  NOTE: shipped to workers by VALUE — the test
    registers this module for cloudpickle by-value pickling, since
    workers cannot import the test module."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    import horovod_tpu as hvt

    hvt.init()
    assert hvt.size() == 2, hvt.size()
    assert jax.local_device_count() == 4
    assert jax.device_count() == 8

    mesh = hvt.world_mesh()
    assert mesh.devices.size == 8

    rng = np.random.RandomState(0)
    W0 = (rng.randn(16, 4) * 0.1).astype(np.float32)
    X = rng.randn(64, 16).astype(np.float32)
    Y = rng.randn(64, 4).astype(np.float32)

    repl = NamedSharding(mesh, P())
    row = NamedSharding(mesh, P("world"))
    w = jax.make_array_from_callback((16, 4), repl, lambda i: W0[i])
    x = jax.make_array_from_callback((64, 16), row, lambda i: X[i])
    y = jax.make_array_from_callback((64, 4), row, lambda i: Y[i])

    opt = hvt.DistributedOptimizer(
        optax.sgd(0.1, momentum=0.9), axis_name="world"
    )

    def step(w, s, xs, ys):
        def loss_fn(w):
            return jnp.mean((xs @ w - ys) ** 2)

        l, g = jax.value_and_grad(loss_fn)(w)
        updates, s = opt.update(g, s, w)
        return optax.apply_updates(w, updates), s, jax.lax.pmean(l, "world")

    sstep = jax.jit(jax.shard_map(
        step, mesh=mesh,
        in_specs=(P(), P(), P("world"), P("world")),
        out_specs=(P(), P(), P()), check_vma=False,
    ))
    s = jax.jit(
        opt.init, out_shardings=jax.tree_util.tree_map(lambda _: repl,
                                                       jax.eval_shape(opt.init, w))
    )(w)

    losses = []
    for _ in range(5):
        w, s, l = sstep(w, s, x, y)
        losses.append(float(np.asarray(l.addressable_data(0))))
    wout = np.asarray(w.addressable_data(0))
    return (hvt.rank(), losses, wout.tolist())


def test_pod_shape_jit_global_mesh_2proc_x_4dev():
    """The flagship jit path on a multi-process global mesh — 2 procs x
    4 CPU devices = 8-device world mesh, XLA compiling per-host programs
    (never previously exercised; every earlier multi-process test ran
    cpu_devices=1 and every 8-device test was single-process)."""
    import numpy as np
    import optax

    import sys

    import cloudpickle

    cloudpickle.register_pickle_by_value(sys.modules[__name__])
    try:
        results = _run(_pod_train_body, np=2, cpu_devices=4)
    finally:
        cloudpickle.unregister_pickle_by_value(sys.modules[__name__])

    # (a) lockstep across the two processes: identical loss trajectory
    # and identical final params
    (r0, losses0, w0), (r1, losses1, w1) = sorted(results)
    assert (r0, r1) == (0, 1)
    np.testing.assert_allclose(losses0, losses1, rtol=0, atol=0)
    np.testing.assert_allclose(w0, w1, rtol=0, atol=0)

    # (b) equivalence with the single-process full-batch reference:
    # grads averaged over the world axis == full-batch gradient
    rng = np.random.RandomState(0)
    W = (rng.randn(16, 4) * 0.1).astype(np.float32)
    X = rng.randn(64, 16).astype(np.float32)
    Y = rng.randn(64, 4).astype(np.float32)
    opt = optax.sgd(0.1, momentum=0.9)
    s = opt.init(W)
    import jax
    import jax.numpy as jnp

    def loss_fn(w):
        return jnp.mean((jnp.asarray(X) @ w - jnp.asarray(Y)) ** 2)

    w = jnp.asarray(W)
    ref_losses = []
    for _ in range(5):
        l, g = jax.value_and_grad(loss_fn)(w)
        upd, s = opt.update(g, s, w)
        w = optax.apply_updates(w, upd)
        ref_losses.append(float(l))
    np.testing.assert_allclose(losses0, ref_losses, rtol=2e-5)
    np.testing.assert_allclose(w0, np.asarray(w), rtol=1e-4, atol=1e-5)


def test_eager_engine_multidevice_2proc_x_2dev():
    """The eager engine's D>1-per-process story: eager collectives are
    PROCESS-granularity (one process = one Horovod rank, contribution
    rides the process's designated transport device); extra local
    devices belong to the jit/SPMD path.  hvt.size() must stay the
    process count and results must match the P=2 semantics exactly."""
    import numpy as np

    def body():
        import jax
        import jax.numpy as jnp
        import numpy as np

        import horovod_tpu as hvt

        hvt.init()
        assert hvt.size() == 2
        assert jax.local_device_count() == 2
        assert jax.device_count() == 4
        r = hvt.rank()
        out = {}
        out["sum"] = np.asarray(
            hvt.allreduce(jnp.full((3,), float(r + 1)), op=hvt.Sum)
        ).tolist()
        out["gather"] = np.asarray(
            hvt.allgather(jnp.full((1, 2), float(r)))
        ).tolist()
        h = hvt.allreduce_async(jnp.full((4,), float(r + 1)), name="pod",
                                op=hvt.Sum)
        out["async"] = np.asarray(hvt.synchronize(h)).tolist()
        out["bcast"] = np.asarray(
            hvt.broadcast(jnp.full((2,), float(r * 7)), root_rank=1)
        ).tolist()
        return (r, out)

    results = _run(body, np=2, cpu_devices=2)
    for _, out in sorted(results):
        assert out["sum"] == [3.0, 3.0, 3.0]
        assert out["gather"] == [[0.0, 0.0], [1.0, 1.0]]
        assert out["async"] == [3.0, 3.0, 3.0, 3.0]
        assert out["bcast"] == [7.0, 7.0]


def test_hierarchical_jit_mesh_2proc_x_4dev():
    """Multi-slice jit collectives on the (dcn, ici) hierarchical mesh
    in the pod shape: 2 processes (dcn axis) x 4 local devices (ici
    axis).  A two-stage allreduce (psum over ici, then dcn) must equal
    the flat world psum — the jit-path analog of the eager
    hierarchical path (comm/eager.py allreduce_hier), closing the loop
    between the pod-shape tests and HVTPU_HIERARCHICAL_ALLREDUCE."""
    import numpy as np

    def body():
        import jax
        import jax.numpy as jnp
        import numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P

        import horovod_tpu as hvt
        from horovod_tpu.comm import spmd
        from horovod_tpu.comm.reduce_ops import ReduceOp

        hvt.init()
        assert hvt.size() == 2 and jax.local_device_count() == 4
        hier = hvt.hierarchical_mesh()
        assert hier.devices.shape == (2, 4)
        assert hier.axis_names == ("dcn", "ici")

        rng = np.random.RandomState(5)
        data = rng.randn(8, 512).astype(np.float32)
        shard = NamedSharding(hier, P(("dcn", "ici")))
        x = jax.make_array_from_callback((8, 512), shard,
                                         lambda i: data[i])

        def two_stage(xs):
            v = xs[0]
            v = spmd.allreduce(v, axis_name="ici", op=ReduceOp.SUM)
            v = spmd.allreduce(v, axis_name="dcn", op=ReduceOp.SUM)
            return v

        out = jax.jit(jax.shard_map(
            two_stage, mesh=hier,
            in_specs=(P(("dcn", "ici")),), out_specs=P(),
            check_vma=False,
        ))(x)
        got = np.asarray(out.addressable_data(0))
        want = data.sum(0)
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
        return hvt.rank()

    results = _run(body, np=2, cpu_devices=4)
    assert sorted(results) == [0, 1]


def test_remote_path_executes_via_ssh_transport(tmp_path):
    """The remote-host launch path EXECUTED, not just string-compared:
    a 2-rank job whose second host is non-local goes through build_ssh_command and a real transport exec
    (a local sh shim standing in for sshd — the sandbox has no ssh
    binary), covering env-export serialization, quoting, cwd, piping
    and exit propagation; the NIC probe supplies the coordinator
    address for the mixed local/remote spec."""
    import subprocess
    import sys

    script = tmp_path / "remote_worker.py"
    script.write_text(
        "import jax\n"
        "import horovod_tpu as hvt\n"
        "hvt.init()\n"
        "import jax.numpy as jnp\n"
        "out = hvt.allreduce(jnp.full((2,), float(hvt.rank() + 1)),"
        " op=hvt.Sum)\n"
        "print(f'REMOTE_OK rank={hvt.rank()} sum={float(out[0])}',"
        " flush=True)\n"
    )
    env = dict(os.environ)
    env["PYTHONPATH"] = _REPO_ROOT + os.pathsep + env.get("PYTHONPATH", "")
    env["HVTPU_SSH_COMMAND"] = (
        f"{sys.executable} {os.path.join(_REPO_ROOT, 'tests', 'fake_ssh.py')}"
    )
    proc = subprocess.run(
        [sys.executable, "-m", "horovod_tpu.runner",
         "-np", "2", "-H", "localhost:1,fakeremote.invalid:1",
         "--cpu-devices", "1",
         "--", sys.executable, str(script)],
        env=env, capture_output=True, text=True, timeout=300,
    )
    out = proc.stdout + proc.stderr
    assert proc.returncode == 0, out[-3000:]
    assert "FAKE_SSH host=fakeremote.invalid" in out, out[-3000:]
    assert "REMOTE_OK rank=0 sum=3.0" in out, out[-3000:]
    assert "REMOTE_OK rank=1 sum=3.0" in out, out[-3000:]


def test_remote_path_propagates_failure(tmp_path):
    """A remote worker's non-zero exit must terminate the job with a
    failing exit code through the same transport."""
    import subprocess
    import sys

    script = tmp_path / "remote_fail.py"
    script.write_text(
        "import os, sys\n"
        "rank = int(os.environ['HVTPU_RANK'])\n"
        "sys.exit(7 if rank == 1 else 0)\n"
    )
    env = dict(os.environ)
    env["PYTHONPATH"] = _REPO_ROOT + os.pathsep + env.get("PYTHONPATH", "")
    env["HVTPU_SSH_COMMAND"] = (
        f"{sys.executable} {os.path.join(_REPO_ROOT, 'tests', 'fake_ssh.py')}"
    )
    proc = subprocess.run(
        [sys.executable, "-m", "horovod_tpu.runner",
         "-np", "2", "-H", "localhost:1,fakeremote.invalid:1",
         "--", sys.executable, str(script)],
        env=env, capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0
    assert "FAKE_SSH" in (proc.stdout + proc.stderr)


def test_adasum_multiprocess_2_and_4proc():
    """Adasum across REAL processes (previously only verified single-
    process against numpy): P=2 and P=4 flat recursive doubling must
    match the numpy reference bit-for-tolerance on every rank."""
    import numpy as np

    from horovod_tpu.comm.adasum import adasum_reduce_reference

    def body():
        import jax.numpy as jnp
        import numpy as np

        import horovod_tpu as hvt

        hvt.init()
        r, s = hvt.rank(), hvt.size()
        rng = np.random.RandomState(40 + r)
        t = rng.randn(33).astype(np.float32)
        out = np.asarray(hvt.allreduce(jnp.asarray(t), op=hvt.Adasum))
        # async path through the controller too
        h = hvt.allreduce_async(jnp.asarray(t * 2.0), name="ad",
                                op=hvt.Adasum)
        out2 = np.asarray(hvt.synchronize(h))
        return (r, out.tolist(), out2.tolist())

    for np_procs in (2, 4):
        results = _run(body, np=np_procs)
        tensors = [
            np.random.RandomState(40 + r).randn(33).astype(np.float32)
            for r in range(np_procs)
        ]
        want = adasum_reduce_reference(tensors)
        want2 = adasum_reduce_reference([t * 2.0 for t in tensors])
        for r, out, out2 in results:
            np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-6)
            np.testing.assert_allclose(out2, want2, rtol=1e-5, atol=1e-6)


def test_hierarchical_adasum_4proc():
    """Hierarchical Adasum on the (dcn, ici) layout (parity:
    adasum_gpu_operations.cc — local SUM within the host, Adasum
    across hosts): 2 hosts x 2 slots must produce
    adasum(host0_sum, host1_sum) on every rank."""
    import numpy as np

    from horovod_tpu.comm.adasum import adasum_reduce_reference

    def body():
        import jax.numpy as jnp
        import numpy as np

        import horovod_tpu as hvt

        hvt.init()
        r = hvt.rank()
        assert hvt.local_size() == 2 and hvt.cross_size() == 2
        rng = np.random.RandomState(50 + r)
        t = rng.randn(17).astype(np.float32)
        out = np.asarray(hvt.allreduce(jnp.asarray(t), op=hvt.Adasum))
        return (r, out.tolist())

    results = run(
        body, np=4, cpu_devices=1,
        hosts="localhost:2,127.0.0.1:2",
        env={**_ENV, "HVTPU_HIERARCHICAL_ALLREDUCE": "1"},
        start_timeout=300.0,
    )
    tensors = [
        np.random.RandomState(50 + r).randn(17).astype(np.float32)
        for r in range(4)
    ]
    # hosts are assigned in sorted order (127.0.0.1 before localhost),
    # but host-sums are symmetric inputs to the pairwise combine, so
    # grouping (0,1) vs (2,3) matches either assignment
    h0 = tensors[0] + tensors[1]
    h1 = tensors[2] + tensors[3]
    want = adasum_reduce_reference([h0, h1])
    for r, out in results:
        np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-6)


def test_eager_multidevice_lanes_2proc_x_4dev():
    """Multi-lane eager allreduce at the pod shape: each process's
    payload is sharded across its 4 local devices (4 parallel
    reduction lanes) with numerics identical to the process-level
    contract, across ops/dtypes/odd sizes (the
    HVTPU_EAGER_MULTIDEVICE=0 fallback is the sibling optout test)."""
    import numpy as np

    def body():
        import os

        import jax
        import jax.numpy as jnp
        import numpy as np

        import horovod_tpu as hvt
        from jax.sharding import Mesh

        hvt.init()
        r = hvt.rank()
        assert hvt.size() == 2 and jax.local_device_count() == 4
        out = {}

        # >= _MULTIDEV_MIN_BYTES so the lane path engages
        x = jnp.arange(100000, dtype=jnp.float32) + 100000.0 * r
        out["sum_ok"] = bool(np.array_equal(
            np.asarray(hvt.allreduce(x, op=hvt.Sum)),
            np.arange(100000) * 2.0 + 100000.0,
        ))
        out["mx"] = np.asarray(
            hvt.allreduce(jnp.full((7,), float(r)), op=hvt.Max)
        ).tolist()
        out["bf16"] = np.asarray(hvt.allreduce(
            jnp.full((9,), 2.0, jnp.bfloat16), op=hvt.Average
        ).astype(jnp.float32)).tolist()
        out["int_avg"] = np.asarray(hvt.allreduce(
            jnp.full((3,), 3 + r, jnp.int32), op=hvt.Average
        )).tolist()

        # lane-parallel broadcast (the broadcast_parameters startup
        # wire): large byte buffer + odd length from a non-zero root
        bb = np.arange(130_001, dtype=np.uint8) + r  # wraps mod 256
        out["bcast_ok"] = bool(np.array_equal(
            np.asarray(hvt.broadcast(jnp.asarray(bb), root_rank=1)),
            (np.arange(130_001) + 1).astype(np.uint8),
        ))

        # the multi-lane mesh actually engaged (cached on the set)
        st = hvt.core.state.global_state()
        gset = st.process_set_table.global_process_set
        out["lanes"] = isinstance(
            getattr(gset, "_multidev_mesh", None), Mesh
        )

        # mid-run env flips must have NO effect: the flag is
        # snapshotted at init (divergent per-process settings would
        # compile mismatched collective programs and hang)
        os.environ["HVTPU_EAGER_MULTIDEVICE"] = "0"
        out["sum_after_flip_ok"] = bool(np.array_equal(
            np.asarray(hvt.allreduce(x, op=hvt.Sum, name="flip")),
            np.arange(100000) * 2.0 + 100000.0,
        ))
        out["lanes_after_flip"] = isinstance(
            getattr(gset, "_multidev_mesh", None), Mesh
        )
        os.environ.pop("HVTPU_EAGER_MULTIDEVICE")
        return (r, out)

    results = _run(body, np=2, cpu_devices=4)
    for _, out in sorted(results):
        assert out["sum_ok"] is True
        assert out["mx"] == [1.0] * 7
        assert out["bf16"] == [2.0] * 9
        assert out["int_avg"] == [3] * 3  # floor((3 + 4)/2)
        assert out["bcast_ok"] is True
        assert out["lanes"] is True
        assert out["sum_after_flip_ok"] is True
        assert out["lanes_after_flip"] is True


def test_eager_multilane_gather_scatter_alltoall_2proc_x_4dev():
    """Round-4: the lane path extended beyond allreduce/broadcast —
    allgather (incl. ragged), reducescatter (Sum + Average), and
    variable-split alltoall move big payloads over all 4 local lanes
    with results IDENTICAL to the small-payload (single-transport)
    path."""
    import numpy as np

    def body():
        import jax
        import jax.numpy as jnp
        import numpy as np

        import horovod_tpu as hvt

        hvt.init()
        r = hvt.rank()
        assert hvt.size() == 2 and jax.local_device_count() == 4
        out = {}

        # big ragged allgather: rank r contributes (r+1)*9000 rows of 3
        big = (jnp.arange((r + 1) * 9000 * 3, dtype=jnp.float32)
               .reshape(-1, 3) + 1e6 * r)
        g = np.asarray(hvt.allgather(big))
        expect = np.concatenate([
            np.arange(9000 * 3, dtype=np.float32).reshape(-1, 3),
            np.arange(2 * 9000 * 3, dtype=np.float32).reshape(-1, 3)
            + 1e6,
        ])
        out["gather_ok"] = bool(np.array_equal(g, expect))

        # big even reducescatter, Sum and Average, odd inner size
        x = (jnp.arange(40_000 * 3, dtype=jnp.float32)
             .reshape(-1, 3) * (r + 1))
        rs = np.asarray(hvt.reducescatter(x, op=hvt.Sum))
        full = (np.arange(40_000 * 3, dtype=np.float32)
                .reshape(-1, 3) * 3.0)
        out["rs_sum_ok"] = bool(np.allclose(
            rs, full[r * 20_000:(r + 1) * 20_000]))
        rsa = np.asarray(hvt.reducescatter(x, op=hvt.Average))
        out["rs_avg_ok"] = bool(np.allclose(
            rsa, full[r * 20_000:(r + 1) * 20_000] / 2.0))

        # big variable-split alltoall
        splits = [30_000, 10_000] if r == 0 else [5_000, 25_000]
        t = (jnp.arange(sum(splits) * 2, dtype=jnp.float32)
             .reshape(-1, 2) + 1e6 * r)
        recv, rsplits = hvt.alltoall(t, splits=splits)
        recv = np.asarray(recv)
        # build expectations from both ranks' send buffers
        t0 = (np.arange(40_000 * 2, dtype=np.float32).reshape(-1, 2))
        t1 = (np.arange(30_000 * 2, dtype=np.float32).reshape(-1, 2)
              + 1e6)
        if r == 0:
            want = np.concatenate([t0[:30_000], t1[:5_000]])
            want_splits = [30_000, 5_000]
        else:
            want = np.concatenate([t0[30_000:40_000], t1[5_000:30_000]])
            want_splits = [10_000, 25_000]
        out["a2a_ok"] = bool(np.array_equal(recv, want))
        out["a2a_splits"] = np.asarray(rsplits).tolist() == want_splits

        # identical numerics when the payload is SMALL (flat path):
        # same ops, sizes below the 64KB lane threshold
        g2 = np.asarray(hvt.allgather(
            jnp.full((r + 1, 2), float(r))))
        out["small_gather_ok"] = bool(np.array_equal(
            g2, np.asarray([[0, 0], [1, 1], [1, 1]], np.float32)))
        return (r, out)

    results = _run(body, np=2, cpu_devices=4)
    for _, out in sorted(results):
        assert out["gather_ok"] is True
        assert out["rs_sum_ok"] is True
        assert out["rs_avg_ok"] is True
        assert out["a2a_ok"] is True
        assert out["a2a_splits"] is True
        assert out["small_gather_ok"] is True


def test_eager_multidevice_optout_2proc_x_4dev():
    """HVTPU_EAGER_MULTIDEVICE=0 (launcher-distributed env):
    single-transport fallback with identical numbers."""
    def body_single():
        import jax.numpy as jnp
        import numpy as np

        import horovod_tpu as hvt

        hvt.init()
        r = hvt.rank()
        x = jnp.arange(100000, dtype=jnp.float32) + 100000.0 * r
        ok = bool(np.array_equal(
            np.asarray(hvt.allreduce(x, op=hvt.Sum)),
            np.arange(100000) * 2.0 + 100000.0,
        ))
        st = hvt.core.state.global_state()
        gset = st.process_set_table.global_process_set
        return (r, ok, getattr(gset, "_multidev_mesh", None) is None)

    results = run(body_single, np=2, cpu_devices=4,
                  env={**_ENV, "HVTPU_EAGER_MULTIDEVICE": "0"},
                  start_timeout=300.0)
    for _, ok, no_lanes in sorted(results):
        assert ok is True
        assert no_lanes


def _split_burst_body():
    """SPMD body for the split-burst divergence matrix: records the
    fused groupings each rank APPLIES (in order) while an injected
    mid-burst delay on rank 1 splits its drained bursts — the exact
    scenario that made v4 schedule prediction unsound.  With atomic
    burst units the coordinator never fuses across a burst boundary,
    so the applied groupings (predicted or negotiated) must stay
    byte-identical across ranks."""
    import jax.numpy as jnp
    import numpy as np

    import horovod_tpu as hvt
    from horovod_tpu.eager import get_controller
    from horovod_tpu.obs import metrics as obs_metrics

    hvt.init()
    r = hvt.rank()
    ctrl = get_controller()
    groupings = []
    orig = ctrl._execute_one

    def spy(rs, payloads):
        groupings.append(list(rs.tensor_names))
        return orig(rs, payloads)

    ctrl._execute_one = spy
    for step in range(14):
        hs = [hvt.allreduce_async(jnp.full((64,), float(step)),
                                  name=f"sb/{i}", op=hvt.Sum)
              for i in range(4)]
        for h in hs:
            out = hvt.synchronize(h)
            assert float(np.asarray(out)[0]) == 2.0 * step, (step, out)
    assert ctrl.quiesce(timeout=20)
    pred = obs_metrics.counter(
        "hvtpu_controller_predicted_cycles_total").value()
    misp = obs_metrics.counter(
        "hvtpu_controller_mispredicts_total").value()
    return (r, groupings, pred, misp, len(ctrl._predicted))


@pytest.mark.chaos
@pytest.mark.parametrize("force_py", ["0", "1"])
@pytest.mark.parametrize("stream", ["0", "1"])
def test_split_burst_groupings_identical_2proc(force_py, stream):
    """Split-burst divergence matrix over {native, py} × {lockstep,
    streamed} with prediction on by default: a 20ms delay injected on
    rank 1 mid-run splits its bursts across drain boundaries; fused
    groupings must stay identical on both ranks, every predicted cycle
    must be confirmed, and nothing may mispredict."""
    import sys

    import cloudpickle

    env = {
        **_ENV,
        "HVTPU_EAGER_STREAM": stream,
        "HVTPU_FAULT_SPEC": "collective.pre:delay(20)@rank=1,count=6,times=4",
    }
    if force_py == "1":
        env["HVTPU_FORCE_PY_CONTROLLER"] = "1"
    cloudpickle.register_pickle_by_value(sys.modules[__name__])
    try:
        results = run(_split_burst_body, np=2, cpu_devices=1, env=env,
                      start_timeout=300.0)
    finally:
        cloudpickle.unregister_pickle_by_value(sys.modules[__name__])
    (r0, g0, p0, m0, out0), (r1, g1, p1, m1, out1) = sorted(results)
    assert (r0, r1) == (0, 1)
    assert g0 == g1, (g0, g1)
    assert m0 == 0 and m1 == 0  # zero mispredicts, recovered or not
    assert out0 == 0 and out1 == 0  # every prediction confirmed
    # every tensor of every step was applied exactly once on each rank
    applied = sorted(n for grp in g0 for n in grp)
    assert applied == sorted([f"sb/{i}" for i in range(4)] * 14)


def test_eager_collectives_8proc():
    """World-size-8 smoke across REAL processes — the largest world
    this sandbox launches (multi-host shape at process granularity):
    sync + async-fused allreduce, ragged allgather, and a broadcast
    stay correct and the amortized stall watchdog stays transparent."""

    def body():
        import jax.numpy as jnp
        import numpy as np

        import horovod_tpu as hvt

        hvt.init()
        r, s = hvt.rank(), hvt.size()
        assert s == 8
        out = {}

        x = jnp.full((64,), float(r + 1))
        out["sum"] = float(np.asarray(
            hvt.allreduce(x, op=hvt.Sum))[0])  # 1+..+8 = 36
        out["avg"] = float(np.asarray(
            hvt.allreduce(x, op=hvt.Average))[0])  # 4.5

        # async fused burst through the controller
        hs = [hvt.allreduce_async(jnp.full((8,), float(r)),
                                  op=hvt.Sum, name=f"t{i}")
              for i in range(4)]
        outs = [float(np.asarray(hvt.synchronize(h))[0]) for h in hs]
        out["async"] = outs  # sum of ranks 0..7 = 28, every tensor

        g = hvt.allgather(jnp.full((r % 2 + 1, 2), float(r)))
        out["gather_rows"] = int(np.asarray(g).shape[0])  # 4*1+4*2=12

        b = hvt.broadcast(jnp.full((2,), float(r)), root_rank=5)
        out["bcast"] = float(np.asarray(b)[0])
        return (r, out)

    # np=8 on localhost occasionally trips a jaxlib/gloo teardown race
    # (one rank SIGSEGVs mid-collective, code -11, and the peers report
    # "Connection closed by peer").  That race is in the gloo transport,
    # not this engine — retry via the named gloo-teardown policy
    # (core/retry.py) so the semantic assertions below still gate every
    # op, but an infra crash alone doesn't flake CI.
    from horovod_tpu.core import retry as core_retry

    results = core_retry.call(core_retry.GLOO_TEARDOWN, _run, body, np=8)
    assert len(results) == 8
    for _, out in sorted(results):
        assert out["sum"] == 36.0
        assert out["avg"] == 4.5
        assert out["async"] == [28.0] * 4
        assert out["gather_rows"] == 12
        assert out["bcast"] == 5.0
