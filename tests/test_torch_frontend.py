"""Torch frontend tests (parity model: test/parallel/test_torch.py in
the reference, §4 of SURVEY.md — op × dtype matrix, in-place semantics,
optimizer behavior).

This sandbox is one process, so collectives degenerate to
identity/size-1 semantics; the multi-rank data path is exercised by the
engine's own tests and by runner integration tests.  What IS fully
tested here: the torch↔engine adapter boundary (dtype/shape/layout
round-trips, in-place contracts, handle lifecycle) and the
DistributedOptimizer's hook/synchronize machinery, which is identical
code at any world size.
"""

import numpy as np
import pytest
import torch

import horovod_tpu.torch as hvd


@pytest.fixture(autouse=True)
def _init():
    hvd.init()
    yield


DTYPES = [torch.float32, torch.float64, torch.int32, torch.int64,
          torch.float16, torch.bfloat16]


class TestOps:
    @pytest.mark.parametrize("dtype", DTYPES)
    def test_allreduce_roundtrip(self, dtype):
        t = torch.arange(17).reshape(17).to(dtype)
        out = hvd.allreduce(t, name=f"ar.{dtype}")
        assert out.dtype == dtype
        assert out.shape == t.shape
        torch.testing.assert_close(out, t)

    def test_fp64_precision_warning(self):
        import warnings as _w
        import horovod_tpu.torch.mpi_ops as mo
        import jax
        if jax.config.jax_enable_x64:
            pytest.skip("x64 enabled: no precision loss to warn about")
        mo._warned_fp64 = False
        with _w.catch_warnings(record=True) as rec:
            _w.simplefilter("always")
            hvd.allreduce(torch.ones(4, dtype=torch.float64), name="w64")
        assert any("float64" in str(r.message) for r in rec)
        # warn-once contract
        with _w.catch_warnings(record=True) as rec2:
            _w.simplefilter("always")
            hvd.allreduce(torch.ones(4, dtype=torch.float64), name="w64b")
        assert not any("float64" in str(r.message) for r in rec2)

    def test_allreduce_noncontiguous(self):
        t = torch.arange(12.0).reshape(3, 4).t()  # non-contiguous view
        out = hvd.allreduce(t, name="ar.nc")
        torch.testing.assert_close(out, t)

    def test_allreduce_inplace(self):
        t = torch.ones(5)
        r = hvd.allreduce_(t, name="ar.ip")
        assert r is t
        torch.testing.assert_close(t, torch.ones(5))

    def test_allreduce_prescale(self):
        t = torch.ones(4)
        out = hvd.allreduce(t, prescale_factor=2.0, name="ar.pre")
        torch.testing.assert_close(out, 2 * torch.ones(4))

    def test_allreduce_compression_fp16(self):
        t = torch.full((8,), 0.5)
        out = hvd.allreduce(t, compression=hvd.Compression.fp16,
                            name="ar.fp16")
        assert out.dtype == torch.float32
        torch.testing.assert_close(out, t)

    def test_grouped_allreduce(self):
        ts = [torch.ones(3), torch.arange(4.0)]
        outs = hvd.grouped_allreduce(ts, name="gar")
        for o, t in zip(outs, ts):
            torch.testing.assert_close(o, t)

    def test_allgather(self):
        t = torch.arange(6.0).reshape(2, 3)
        out = hvd.allgather(t)
        assert out.shape == (2 * hvd.size(), 3)

    def test_broadcast_inplace(self):
        t = torch.randn(4, 4)
        want = t.clone()
        r = hvd.broadcast_(t, root_rank=0)
        assert r is t
        torch.testing.assert_close(t, want)

    def test_alltoall(self):
        t = torch.arange(8.0)
        out = hvd.alltoall(t)
        torch.testing.assert_close(out, t)

    def test_alltoall_with_splits(self):
        t = torch.arange(6.0)
        out, rsplits = hvd.alltoall(t, splits=torch.tensor([6]))
        torch.testing.assert_close(out, t)
        assert int(rsplits.sum()) == 6

    def test_reducescatter(self):
        t = torch.arange(8.0)
        out = hvd.reducescatter(t)
        assert out.numel() == 8 // hvd.size()

    def test_async_handle_lifecycle(self):
        t = torch.ones(4)
        h = hvd.allreduce_async(t, name="as.1")
        out = hvd.synchronize(h)
        torch.testing.assert_close(out, t)

    def test_async_inplace(self):
        t = torch.full((3,), 2.0)
        h = hvd.allreduce_async_(t, name="as.2")
        r = hvd.synchronize(h)
        assert r is t
        torch.testing.assert_close(t, torch.full((3,), 2.0))

    def test_broadcast_object(self):
        obj = {"a": torch.ones(2), "b": [1, 2, 3]}
        out = hvd.broadcast_object(obj, root_rank=0)
        torch.testing.assert_close(out["a"], obj["a"])
        assert out["b"] == obj["b"]

    def test_broadcast_parameters(self):
        model = torch.nn.Linear(4, 2)
        hvd.broadcast_parameters(model.state_dict(), root_rank=0)


class TestRegisteredGradients:
    """torch.autograd.Function adjoints on the bare collectives
    (parity: the HorovodAllreduce/... Function wrappers in
    horovod/torch/mpi_ops.py).  Size-1 closed forms; cross-rank
    behavior mirrors the TF suite's multiprocess coverage."""

    def test_allreduce_grad_is_allreduce_of_grad(self, hvt):
        x = torch.tensor([1.0, 2.0, 3.0], requires_grad=True)
        hvd.allreduce(x * 2.0, op=hvd.Sum).sum().backward()
        assert x.grad.tolist() == [2.0, 2.0, 2.0]

    def test_allreduce_minmax_grad_rejected(self, hvt):
        x = torch.tensor([1.0], requires_grad=True)
        y = hvd.allreduce(x, op=hvd.Min)
        with pytest.raises(NotImplementedError, match="MIN"):
            y.backward()

    def test_allgather_grad_slices_own_rows(self, hvt):
        x = torch.ones((2, 1), requires_grad=True)
        (hvd.allgather(x)
         * torch.tensor([[2.0], [5.0]])).sum().backward()
        assert x.grad.ravel().tolist() == [2.0, 5.0]

    def test_broadcast_grad_reduces_to_root(self, hvt):
        x = torch.ones(2, requires_grad=True)
        (hvd.broadcast(x, root_rank=0) * 3.0).sum().backward()
        assert x.grad.tolist() == [3.0, 3.0]

    def test_reducescatter_grad_is_allgather(self, hvt):
        x = torch.ones((2, 1), requires_grad=True)
        (hvd.reducescatter(x, op=hvd.Sum) * 7.0).sum().backward()
        assert x.grad.ravel().tolist() == [7.0, 7.0]

    def test_alltoall_grad_routes_back(self, hvt):
        x = torch.arange(3.0, requires_grad=True)
        out, _ = hvd.alltoall(x, splits=[3])
        (out * 5.0).sum().backward()
        assert x.grad.tolist() == [5.0, 5.0, 5.0]
        x = torch.arange(2.0, requires_grad=True)
        (hvd.alltoall(x) * 2.0).sum().backward()
        assert x.grad.tolist() == [2.0, 2.0]

    def test_grouped_allreduce_grad(self, hvt):
        xs = [torch.ones(2, requires_grad=True),
              torch.ones(3, requires_grad=True)]
        outs = hvd.grouped_allreduce(xs, op=hvd.Sum)
        (outs[0] * 2.0).sum().add((outs[1] * 3.0).sum()).backward()
        assert xs[0].grad.tolist() == [2.0, 2.0]
        assert xs[1].grad.tolist() == [3.0, 3.0, 3.0]

    def test_grouped_allreduce_mixed_grad_list(self, hvt):
        # a grad-free tensor in the group must come back grad-free
        # (e.g. .numpy() on it keeps working) while its peer still
        # backprops
        p = torch.ones(2, requires_grad=True)
        d = torch.ones(2)
        outs = hvd.grouped_allreduce([p, d], op=hvd.Sum)
        assert not outs[1].requires_grad
        outs[1].numpy()  # must not raise
        (outs[0] * 4.0).sum().backward()
        assert p.grad.tolist() == [4.0, 4.0]
        assert d.grad is None

    def test_no_grad_path_unchanged(self, hvt):
        # detached inputs keep the plain zero-overhead route and
        # produce grad-free outputs
        x = torch.ones(3)
        out = hvd.allreduce(x, op=hvd.Sum)
        assert not out.requires_grad


class TestDistributedOptimizer:
    def _model_and_data(self):
        torch.manual_seed(0)
        model = torch.nn.Sequential(
            torch.nn.Linear(8, 16), torch.nn.ReLU(), torch.nn.Linear(16, 4)
        )
        x = torch.randn(32, 8)
        y = torch.randint(0, 4, (32,))
        return model, x, y

    def test_trains(self):
        model, x, y = self._model_and_data()
        opt = hvd.DistributedOptimizer(
            torch.optim.SGD(model.parameters(), lr=0.1, momentum=0.9),
            named_parameters=model.named_parameters(),
        )
        losses = []
        for _ in range(20):
            opt.zero_grad()
            loss = torch.nn.functional.cross_entropy(model(x), y)
            loss.backward()
            opt.step()
            losses.append(float(loss))
        assert losses[-1] < losses[0] * 0.9

    def test_matches_plain_sgd_size1(self):
        """At size 1, DistributedOptimizer must be numerically identical
        to the wrapped optimizer."""
        model1, x, y = self._model_and_data()
        model2 = torch.nn.Sequential(
            torch.nn.Linear(8, 16), torch.nn.ReLU(), torch.nn.Linear(16, 4)
        )
        model2.load_state_dict(model1.state_dict())

        opt1 = hvd.DistributedOptimizer(
            torch.optim.SGD(model1.parameters(), lr=0.1, momentum=0.9),
            named_parameters=model1.named_parameters(),
        )
        opt2 = torch.optim.SGD(model2.parameters(), lr=0.1, momentum=0.9)
        for _ in range(3):
            for opt, model in ((opt1, model1), (opt2, model2)):
                opt.zero_grad()
                torch.nn.functional.cross_entropy(model(x), y).backward()
                opt.step()
        for p1, p2 in zip(model1.parameters(), model2.parameters()):
            torch.testing.assert_close(p1, p2)

    def test_backward_passes_per_step(self):
        model, x, y = self._model_and_data()
        opt = hvd.DistributedOptimizer(
            torch.optim.SGD(model.parameters(), lr=0.05),
            named_parameters=model.named_parameters(),
            backward_passes_per_step=2,
        )
        opt.zero_grad()
        torch.nn.functional.cross_entropy(model(x[:16]), y[:16]).backward()
        torch.nn.functional.cross_entropy(model(x[16:]), y[16:]).backward()
        opt.step()  # accumulated 2 passes, then stepped

    def test_too_many_passes_raises(self):
        model, x, y = self._model_and_data()
        opt = hvd.DistributedOptimizer(
            torch.optim.SGD(model.parameters(), lr=0.05),
            named_parameters=model.named_parameters(),
        )
        opt.zero_grad()
        torch.nn.functional.cross_entropy(model(x), y).backward()
        with pytest.raises(AssertionError, match="more than"):
            torch.nn.functional.cross_entropy(model(x), y).backward()
        # drain the first backward's pending handles so their names
        # don't race the next test's enqueues
        opt.synchronize()

    def test_zero_grad_mid_cycle_raises(self):
        model, x, y = self._model_and_data()
        opt = hvd.DistributedOptimizer(
            torch.optim.SGD(model.parameters(), lr=0.05),
            named_parameters=model.named_parameters(),
        )
        opt.zero_grad()
        torch.nn.functional.cross_entropy(model(x), y).backward()
        with pytest.raises(AssertionError, match="zero_grad"):
            opt.zero_grad()
        opt.synchronize()  # clean up

    def test_predivide_requires_average(self):
        model, _, _ = self._model_and_data()
        with pytest.raises(ValueError, match="predivide"):
            hvd.DistributedOptimizer(
                torch.optim.SGD(model.parameters(), lr=0.1),
                named_parameters=model.named_parameters(),
                op=hvd.Sum, gradient_predivide_factor=2.0,
            )

    def test_predivide_postscale_uses_process_set_size(self, monkeypatch):
        # Average emulation must divide by the participating-rank count
        # (the process set's size), not the world size.
        model, _, _ = self._model_and_data()
        opt = hvd.DistributedOptimizer(
            torch.optim.SGD(model.parameters(), lr=0.1),
            named_parameters=model.named_parameters(),
            gradient_predivide_factor=2.0,
        )

        from horovod_tpu.core.process_set import ProcessSet
        ps = ProcessSet([0, 1])
        opt._process_set = ps

        import horovod_tpu.torch.optimizer as opt_mod
        monkeypatch.setattr(opt_mod._hvt, "size", lambda: 8)
        seen = {}

        def fake_async(grad, name, op, compression, prescale_factor,
                       postscale_factor, process_set):
            seen["post"] = postscale_factor
            return 0
        monkeypatch.setattr(opt_mod.mpi_ops, "allreduce_async_", fake_async)
        p = opt._requires_update[0]
        p.grad = torch.zeros_like(p)
        opt._allreduce_grad_async(p)
        assert seen["post"] == pytest.approx(2.0 / 2)

    def test_skip_synchronize(self):
        model, x, y = self._model_and_data()
        opt = hvd.DistributedOptimizer(
            torch.optim.SGD(model.parameters(), lr=0.1),
            named_parameters=model.named_parameters(),
        )
        opt.zero_grad()
        torch.nn.functional.cross_entropy(model(x), y).backward()
        opt.synchronize()
        with opt.skip_synchronize():
            opt.step()

    def test_isinstance_preserved(self):
        model, _, _ = self._model_and_data()
        opt = hvd.DistributedOptimizer(
            torch.optim.SGD(model.parameters(), lr=0.1),
            named_parameters=model.named_parameters(),
        )
        assert isinstance(opt, torch.optim.SGD)


class TestSyncBatchNorm:
    def test_matches_batchnorm_size1(self):
        torch.manual_seed(1)
        x = torch.randn(8, 3, 4, 4)
        bn = torch.nn.BatchNorm2d(3)
        sbn = hvd.SyncBatchNorm(3)
        sbn.load_state_dict(bn.state_dict())
        bn.train(), sbn.train()
        torch.testing.assert_close(sbn(x), bn(x))

    def test_eval_mode(self):
        sbn = hvd.SyncBatchNorm(3)
        sbn.eval()
        x = torch.randn(2, 3, 4)
        assert sbn(x).shape == x.shape

    def test_grad_flows(self):
        sbn = hvd.SyncBatchNorm(4)
        sbn.train()
        x = torch.randn(6, 4, requires_grad=True)
        sbn(x).sum().backward()
        assert x.grad is not None
        assert sbn.weight.grad is not None

    def test_affine_false_backward(self):
        # affine=False: forward's weight/bias are None — backward must
        # return None grads at those slots or autograd raises.
        from horovod_tpu.torch.sync_batch_norm import _SyncBatchNormFn
        x = torch.randn(6, 4, requires_grad=True)
        out = _SyncBatchNormFn.apply(
            x, None, None, None, None, 1e-5, 0.1, None)
        out.sum().backward()
        assert x.grad is not None

    def test_affine_false_module(self):
        sbn = hvd.SyncBatchNorm(4, affine=False)
        sbn.train()
        x = torch.randn(6, 4, requires_grad=True)
        sbn(x).sum().backward()
        assert x.grad is not None


class TestZeroCopyAdapter:
    """The DLPack adapter boundary : contiguous
    fp32 tensors must cross torch->jax and jax->torch with NO host
    copy, asserted by buffer pointer identity."""

    def test_torch_to_jax_pointer_identity(self, hvt):
        from horovod_tpu.torch.mpi_ops import _to_jax

        t = torch.arange(16, dtype=torch.float32)
        j = _to_jax(t)
        assert t.data_ptr() == j.unsafe_buffer_pointer()

    def test_jax_to_torch_pointer_identity(self, hvt):
        import jax.numpy as jnp

        from horovod_tpu.torch.mpi_ops import _from_jax

        j = jnp.arange(8.0)
        t = _from_jax(j)
        assert t.data_ptr() == j.unsafe_buffer_pointer()

    def test_bf16_rides_dlpack(self, hvt):
        from horovod_tpu.torch.mpi_ops import _to_jax

        t = torch.ones(8, dtype=torch.bfloat16)
        j = _to_jax(t)
        assert str(j.dtype) == "bfloat16"
        assert t.data_ptr() == j.unsafe_buffer_pointer()
        out = hvd.allreduce(t, op=hvd.Sum, name="bf16zc")
        assert out.dtype == torch.bfloat16

    def test_noncontiguous_falls_back(self, hvt):
        t = torch.arange(16, dtype=torch.float32).reshape(4, 4).t()
        out = hvd.allreduce(t, op=hvd.Sum, name="nc")
        assert torch.allclose(out, t)


class TestSparseAllreduce:
    def test_sparse_allreduce_roundtrip(self, hvt):
        i = torch.tensor([[0, 2, 0]])
        v = torch.tensor([[1.0, 2.0], [3.0, 4.0], [10.0, 20.0]])
        sp = torch.sparse_coo_tensor(i, v, size=(4, 2))
        h = hvd.sparse_allreduce_async(sp, name="sp1", op=hvd.Sum)
        out = hvd.synchronize(h)
        assert out.is_sparse
        dense = out.to_dense()
        # duplicate index 0 coalesced: [11, 22]
        assert dense[0].tolist() == [11.0, 22.0]
        assert dense[2].tolist() == [3.0, 4.0]
        assert dense[1].tolist() == [0.0, 0.0]

    def test_sparse_average(self, hvt):
        i = torch.tensor([[1]])
        v = torch.tensor([[8.0]])
        sp = torch.sparse_coo_tensor(i, v, size=(3, 1))
        out = hvd.synchronize(
            hvd.sparse_allreduce_async(sp, name="sp2", op=hvd.Average)
        )
        assert out.to_dense()[1].item() == 8.0  # size-1 world

    def test_dense_tensor_rejected(self, hvt):
        with pytest.raises(ValueError, match="sparse"):
            hvd.sparse_allreduce_async(torch.ones(3), name="d")

    def test_embedding_sparse_grads_through_optimizer(self, hvt):
        emb = torch.nn.Embedding(10, 4, sparse=True)
        opt = torch.optim.SGD(emb.parameters(), lr=0.5)
        opt = hvd.DistributedOptimizer(
            opt, named_parameters=emb.named_parameters()
        )
        w0 = emb.weight.detach().clone()
        idx = torch.tensor([1, 3, 1])
        loss = emb(idx).sum()
        opt.zero_grad()
        loss.backward()
        assert emb.weight.grad.is_sparse
        opt.step()
        moved = (emb.weight.detach() - w0).abs().sum(dim=1)
        assert moved[1] > 0 and moved[3] > 0
        assert moved[0] == 0 and moved[2] == 0

    def test_embedding_sparse_as_dense(self, hvt):
        emb = torch.nn.Embedding(6, 2, sparse=True)
        opt = torch.optim.SGD(emb.parameters(), lr=0.5)
        opt = hvd.DistributedOptimizer(
            opt, named_parameters=emb.named_parameters(),
            sparse_as_dense=True,
        )
        loss = emb(torch.tensor([0, 5])).sum()
        opt.zero_grad()
        loss.backward()
        opt.step()
        assert not emb.weight.grad.is_sparse


class TestFusedBroadcastParameters:
    def test_mixed_dtype_state_dict(self, hvt):
        """The fused byte-buffer path must handle fp32 + bf16 + int64
        buffers in one broadcast and leave values intact (size-1
        world: identity)."""
        model = torch.nn.Sequential(
            torch.nn.Linear(4, 3), torch.nn.BatchNorm1d(3)
        )
        model = model.to(torch.float32)
        sd = model.state_dict()
        before = {k: v.clone() for k, v in sd.items()}
        hvd.broadcast_parameters(sd, root_rank=0)
        for k, v in sd.items():
            assert torch.equal(v, before[k]), k

    def test_single_tensor_falls_through(self, hvt):
        p = torch.nn.Parameter(torch.randn(5))
        before = p.detach().clone()
        hvd.broadcast_parameters([("w", p)], root_rank=0)
        assert torch.equal(p.detach(), before)


class TestEagerBenchRegression:
    """The torch adapter itself, not a record of it: a sync dispatch
    stays off the pathological paths (a generous bound on the CPU,
    no device number) and the async/fused hop stays zero-copy."""

    def test_sync_dispatch_overhead_bound(self, hvt):
        """Small-tensor sync allreduce dispatch must stay in the
        sub-10ms regime; a regression to a pathological path (host
        copy of a large staging buffer, blocking re-trace per call)
        lands well above the generous 50 ms CI bound."""
        import time

        t = torch.ones(64 * 1024 // 4, dtype=torch.float32)
        for i in range(3):
            hvd.allreduce(t, op=hvd.Sum, name=f"bench_warm{i}")
        times = []
        for i in range(10):
            t0 = time.perf_counter()
            hvd.allreduce(t, op=hvd.Sum, name=f"bench_sync{i}")
            times.append(time.perf_counter() - t0)
        med = sorted(times)[len(times) // 2]
        assert med < 0.050, f"sync dispatch {med*1e3:.1f} ms"

    def test_async_fused_path_zero_copy(self, hvt, monkeypatch):
        """The async/fused path must keep the torch->jax hop
        zero-copy: every contiguous fp32 tensor that enters
        allreduce_async crosses the adapter with pointer identity
        (extends the sync-path data_ptr assertion to the fused path)."""
        from horovod_tpu.torch import mpi_ops as mo

        pairs = []
        real = mo._to_jax

        def spy(t):
            j = real(t)
            if (isinstance(t, torch.Tensor) and t.is_contiguous()
                    and t.dtype == torch.float32):
                pairs.append((t.data_ptr(), j.unsafe_buffer_pointer()))
            return j

        monkeypatch.setattr(mo, "_to_jax", spy)
        tensors = [torch.full((1024,), float(i)) for i in range(8)]
        handles = [mo.allreduce_async(t, op=hvd.Sum, name=f"zc{i}")
                   for i, t in enumerate(tensors)]
        outs = [hvd.synchronize(h) for h in handles]
        assert len(pairs) == 8
        for tp, jp in pairs:
            assert tp == jp, "async adapter hop made a host copy"
        for i, o in enumerate(outs):
            assert torch.allclose(o, torch.full((1024,), float(i)))
