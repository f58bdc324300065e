"""hvtpu.data: elastic-aware sharded input pipeline (ISSUE PR 9).

Units: permutation determinism, remainder re-sharding across a resize,
uneven-tail agreement, prefetch shutdown hygiene, exactly-once delivery
with rollback/restore, the elastic participant protocol, the
``data.next`` fault site, and the loader's observability surface.

Acceptance (slow/multiprocess): a 2-proc elastic run preempted
mid-epoch delivers every sample index exactly once across incarnations
(resuming from the drain-committed cursor), and ``hvtputrace report``
attributes an injected ``data.next:delay`` to the input phase of the
right rank.
"""

import os
import re
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import horovod_tpu
from horovod_tpu import data as hvt_data
from horovod_tpu.core import faults
from horovod_tpu.data import (ArraySource, ElasticDataLoader,
                              FileListSource, LoaderState, Sharder,
                              SyntheticSource, sharder)

_REPO = os.path.dirname(os.path.dirname(horovod_tpu.__file__))
_SCRIPT = os.path.join(os.path.dirname(__file__),
                       "elastic_data_script.py")


@pytest.fixture(autouse=True)
def _clean_faults():
    yield
    faults.uninstall()


def _make_loader(n=24, batch=4, **kw):
    kw.setdefault("device_put", False)
    src = ArraySource({"y": np.arange(n)})
    return ElasticDataLoader(src, batch_size=batch, **kw)


# ---------------------------------------------------------------------------
# sharder units
# ---------------------------------------------------------------------------

class TestSharder:
    def test_permutation_deterministic_per_seed_and_epoch(self):
        a = sharder.epoch_permutation(100, seed=5, epoch=3)
        b = sharder.epoch_permutation(100, seed=5, epoch=3)
        assert np.array_equal(a, b)
        assert sorted(a.tolist()) == list(range(100))
        # different epoch (or seed) -> different order, same sample set
        c = sharder.epoch_permutation(100, seed=5, epoch=4)
        d = sharder.epoch_permutation(100, seed=6, epoch=3)
        assert not np.array_equal(a, c)
        assert not np.array_equal(a, d)
        assert sorted(c.tolist()) == list(range(100))

    def test_no_shuffle_is_identity(self):
        p = sharder.epoch_permutation(7, seed=9, epoch=2, shuffle=False)
        assert p.tolist() == list(range(7))

    def test_world_consumes_disjoint_covering_shards(self):
        """One step: the per-rank pieces partition the window."""
        sh = Sharder(40, batch_size=4, seed=1)
        pieces = [sh.next_indices(epoch=0, cursor=0, rank=r, size=3)[0]
                  for r in range(3)]
        cursors = {sh.next_indices(0, 0, r, 3)[1] for r in range(3)}
        assert cursors == {12}  # all ranks agree on the new cursor
        flat = np.concatenate(pieces)
        assert len(flat) == 12 and len(set(flat.tolist())) == 12

    def test_resize_resharding_exactly_once(self):
        """Consume part of an epoch at size 3, finish it at size 2:
        the unconsumed remainder is re-split with nothing repeated or
        dropped — the tentpole's resize contract, in pure math."""
        n, batch = 40, 4
        sh = Sharder(n, batch, seed=11)
        delivered, cursor = [], 0
        for _ in range(2):  # two steps at size 3 (24 samples)
            for r in range(3):
                piece, nxt = sh.next_indices(0, cursor, r, 3)
                delivered.extend(piece.tolist())
            cursor = nxt
        assert cursor == 24
        # "relaunch" with size 2: a fresh Sharder (new incarnation)
        sh2 = Sharder(n, batch, seed=11)
        while cursor < n:
            for r in range(2):
                piece, nxt = sh2.next_indices(0, cursor, r, 2)
                delivered.extend(piece.tolist())
            cursor = nxt
        assert sorted(delivered) == list(range(n))

    def test_uneven_tail_agreement(self):
        """n=10, B=4, size=3: every rank computes the same step count;
        tail pieces differ by <= 1 and may be empty, and the world
        still covers every sample exactly once."""
        n, batch, size = 10, 4, 3
        assert all(
            sharder.steps_remaining(n, 0, size, batch) == 1
            for _ in range(size))
        sh = Sharder(n, batch, seed=2)
        pieces = [sh.next_indices(0, 0, r, size)[0] for r in range(size)]
        sizes = sorted(len(p) for p in pieces)
        assert max(sizes) - min(sizes) <= 1
        flat = np.concatenate(pieces)
        assert sorted(flat.tolist()) == sorted(
            sh.permutation(0)[:n].tolist())
        # a tail shorter than the world leaves trailing ranks empty
        sh5 = Sharder(2, 4, seed=2)
        tail = [sh5.next_indices(0, 0, r, 5)[0] for r in range(5)]
        assert [len(p) for p in tail].count(0) == 3

    def test_steps_remaining_is_rank_independent_mid_epoch(self):
        for cursor in (0, 7, 12, 39, 40):
            vals = {sharder.steps_remaining(40, cursor, 4, 4)}
            assert len(vals) == 1


# ---------------------------------------------------------------------------
# sources
# ---------------------------------------------------------------------------

class TestSources:
    def test_array_source_structure_gather(self):
        src = ArraySource({"x": np.arange(12).reshape(6, 2),
                           "y": np.arange(6)})
        b = src.fetch(np.array([4, 1]))
        assert b["x"].tolist() == [[8, 9], [2, 3]]
        assert b["y"].tolist() == [4, 1]

    def test_array_source_rejects_ragged(self):
        with pytest.raises(ValueError, match="disagree"):
            ArraySource({"x": np.zeros(4), "y": np.zeros(5)})

    def test_file_list_source(self, tmp_path):
        paths = []
        for i in range(4):
            p = tmp_path / f"s{i}.npy"
            np.save(p, np.full((3,), i))
            paths.append(str(p))
        src = FileListSource(paths, labels=[10, 11, 12, 13])
        x, y = src.fetch(np.array([2, 0]))
        assert x.shape == (2, 3) and x[0, 0] == 2
        assert y.tolist() == [12, 10]

    def test_synthetic_source_deterministic(self):
        a = SyntheticSource(100, (4, 4), seed=3)
        b = SyntheticSource(100, (4, 4), seed=3)
        ba, bb = a.fetch(np.arange(5)), b.fetch(np.arange(5))
        assert np.array_equal(ba["x"], bb["x"])
        assert np.array_equal(ba["y"], bb["y"])
        assert ba["x"].shape == (5, 4, 4)


# ---------------------------------------------------------------------------
# ArraySource's block pool: where a gather's memory comes from
# ---------------------------------------------------------------------------

_ROW_BYTES = 1 << 16    # 16 rows make the pooled size of 1 MiB


def _pool_data(dtype, structure, n):
    """``n`` rows of 64 KiB of random bits (NaN patterns among them:
    compare bits, not values) as one big leaf, alone or beside a small
    label leaf that never reaches the pooled size."""
    raw = np.random.default_rng(7).integers(
        0, 256, (n, _ROW_BYTES), dtype=np.uint8)
    big = raw.view(np.dtype(dtype)).reshape(n, -1, 64)
    small = np.arange(n, dtype=np.int32)
    return {"array": big, "dict": {"x": big, "y": small},
            "tuple": (big, small)}[structure]


def _leaves(struct):
    out = []
    hvt_data.map_structure(out.append, struct)
    return out


def _same_bits(got, want):
    got, want = _leaves(got), _leaves(want)
    return len(got) == len(want) and all(
        g.dtype == w.dtype and g.shape == w.shape
        and np.array_equal(np.asarray(g).view(np.uint8), w.view(np.uint8))
        for g, w in zip(got, want))


def _rows(data, indices):
    return hvt_data.map_structure(lambda a: a[indices], data)


def _address(batch):
    return _leaves(batch)[0].ctypes.data


def _block_counts():
    from horovod_tpu.obs import metrics as obs_metrics

    fam = obs_metrics.REGISTRY.counter("hvtpu_data_fetch_blocks_total")
    return fam.value(block="reused"), fam.value(block="fresh")


def _bfloat16():
    import ml_dtypes

    return ml_dtypes.bfloat16


@pytest.mark.parametrize("structure", ["array", "dict", "tuple"])
@pytest.mark.parametrize("dtype", [_bfloat16(), np.float32, np.int32],
                         ids=["bfloat16", "float32", "int32"])
class TestArraySourceBlocks:
    def test_every_batch_of_three_epochs_is_the_pools_rows(
            self, dtype, structure):
        data = _pool_data(dtype, structure, 48)
        reused0, _ = _block_counts()
        ld = ElasticDataLoader(ArraySource(data), batch_size=16, seed=11,
                               device_put=False, with_indices=True)
        seen = 0
        try:
            for _ in range(3):
                for indices, batch in ld:
                    assert _same_bits(batch, _rows(data, indices))
                    seen += 1
                    del batch
        finally:
            ld.close()
        assert seen == 9
        assert _block_counts()[0] > reused0     # and blocks were reused

    def test_a_consumer_that_keeps_its_batches_finds_them_unchanged(
            self, dtype, structure):
        data = _pool_data(dtype, structure, 160)
        reused0, fresh0 = _block_counts()
        ld = ElasticDataLoader(ArraySource(data), batch_size=16, seed=5,
                               device_put=False, with_indices=True)
        try:
            kept = list(ld)
        finally:
            ld.close()
        assert len(kept) == 10          # more than the pool's cap a leaf
        for indices, batch in kept:
            assert _same_bits(batch, _rows(data, indices))
        assert len({_address(b) for _, b in kept}) == 10
        reused, fresh = _block_counts()
        # nothing was free while every batch was held (the prefetcher
        # ran a few fetches past the epoch's end besides)
        assert reused == reused0 and fresh - fresh0 >= 10

    def test_a_consumer_that_drops_its_batches_sees_a_block_again(
            self, dtype, structure):
        data = _pool_data(dtype, structure, 48)
        src = ArraySource(data)
        rng = np.random.default_rng(3)
        reused0, fresh0 = _block_counts()
        batch = src.fetch(rng.permutation(48)[:16])
        first = _address(batch)
        del batch
        for k in range(5):
            indices = rng.permutation(48)[:16]
            batch = src.fetch(indices)
            assert _address(batch) == first
            assert _same_bits(batch, _rows(data, indices))
            del batch
        assert _block_counts() == (reused0 + 5, fresh0 + 1)
        # a batch of another size has a block of its own
        batch = src.fetch(np.arange(20))
        assert _address(batch) != first
        assert _same_bits(batch, _rows(data, np.arange(20)))

    def test_device_batches_outlive_later_fetches(self, dtype, structure):
        data = _pool_data(dtype, structure, 48)
        ld = ElasticDataLoader(ArraySource(data), batch_size=16, seed=2,
                               device_put=True, with_indices=True)
        try:
            kept = list(ld) + list(ld)
        finally:
            ld.close()
        import jax

        assert len(kept) == 6
        assert all(isinstance(leaf, jax.Array)
                   for _, b in kept for leaf in _leaves(b))
        for indices, batch in kept:
            assert _same_bits(batch, _rows(data, indices))

    def test_odd_indices_give_what_indexing_gives(self, dtype, structure):
        data = _pool_data(dtype, structure, 48)
        src = ArraySource(data)
        with pytest.raises(IndexError):
            src.fetch(np.r_[np.arange(15), 48])
        with pytest.raises(IndexError):
            src.fetch(np.r_[np.arange(15), -49])
        negative = np.r_[np.arange(15), -1]
        assert _same_bits(src.fetch(negative), _rows(data, negative))
        empty = np.empty((0,), dtype=np.int64)
        assert _same_bits(src.fetch(empty), _rows(data, empty))
        as_list = list(range(16))
        assert _same_bits(src.fetch(as_list), _rows(data, as_list))

    def test_two_threads_never_receive_the_same_block(
            self, dtype, structure):
        data = _pool_data(dtype, structure, 48)
        src = ArraySource(data)
        held, lock, errors = set(), threading.Lock(), []

        def work(seed):
            rng = np.random.default_rng(seed)
            try:
                for _ in range(25):
                    indices = rng.permutation(48)[:16]
                    batch = src.fetch(indices)
                    address = _address(batch)
                    with lock:
                        assert address not in held
                        held.add(address)
                    time.sleep(0.001)   # the other thread fetches now
                    assert _same_bits(batch, _rows(data, indices))
                    with lock:
                        held.discard(address)
                    del batch
            except BaseException as e:  # noqa: BLE001 - reported below
                errors.append(e)

        threads = [threading.Thread(target=work, args=(k,))
                   for k in range(2)]
        interval = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)     # switch inside the pool's check
        try:
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=60)
        finally:
            sys.setswitchinterval(interval)
        assert not any(t.is_alive() for t in threads)
        assert not errors, errors


@pytest.mark.parametrize("how", ["strided", "memmap", "unsigned_indices"])
def test_what_take_would_copy_whole_keeps_plain_indexing(how, tmp_path):
    """np.take first copies a source that is not C-contiguous; such a
    leaf, an ndarray subclass and indices that are not signed integers
    get ``a[indices]`` as ever, and the pool is not touched."""
    rows = np.random.default_rng(1).integers(
        0, 256, (48, 2, _ROW_BYTES), dtype=np.uint8)
    indices = np.random.default_rng(2).permutation(48)[:16]
    if how == "strided":
        data = rows[:, 0, :]
        assert not data.flags.c_contiguous
    elif how == "memmap":
        data = np.memmap(tmp_path / "rows", dtype=np.uint8, mode="w+",
                         shape=(48, _ROW_BYTES))
        data[:] = rows[:, 1, :]
    else:
        data, indices = rows[:, 0, :].copy(), indices.astype(np.uint32)
    before = _block_counts()
    src = ArraySource(data)
    for _ in range(3):
        assert _same_bits(src.fetch(indices), np.asarray(data)[indices])
    assert _block_counts() == before


# ---------------------------------------------------------------------------
# loader units
# ---------------------------------------------------------------------------

class TestLoader:
    def test_epoch_delivers_each_sample_once(self):
        ld = _make_loader(n=24, batch=4, seed=3)
        try:
            seen = [int(v) for b in ld for v in b["y"]]
            assert sorted(seen) == list(range(24))
            assert ld.state.epoch == 1 and ld.state.cursor == 0
        finally:
            ld.close()

    def test_prefetch_shutdown_leaves_no_thread(self):
        ld = _make_loader(n=24, batch=4)
        it = iter(ld)
        next(it)
        names = [t.name for t in threading.enumerate()]
        assert any("hvtpu-data-prefetch" in s for s in names)
        ld.close()
        deadline = time.time() + 5
        while time.time() < deadline:
            names = [t.name for t in threading.enumerate()]
            if not any("hvtpu-data-prefetch" in s for s in names):
                break
            time.sleep(0.05)
        assert not any("hvtpu-data-prefetch" in s for s in names)

    def test_restore_replays_only_uncommitted_batches(self):
        """Rollback semantics: restore() rewinds to the last commit;
        exactly the uncommitted batches are re-delivered (prefetched-
        but-undelivered data never counts as consumed)."""
        from horovod_tpu import elastic

        ld = _make_loader(n=24, batch=4, seed=5)
        try:
            st = elastic.ObjectState(data=ld.state, step=0)
            it = iter(ld)
            committed = [next(it)["y"].tolist(), next(it)["y"].tolist()]
            st.save_to_memory()
            lost = [next(it)["y"].tolist(), next(it)["y"].tolist()]
            st.restore()
            assert ld.state is st.data, "in-place restore lost identity"
            assert ld.state.cursor == 8
            replay = [b["y"].tolist() for b in ld]
            assert replay[0] == lost[0] and replay[1] == lost[1]
            everything = [v for b in committed + replay for v in b]
            assert sorted(everything) == list(range(24))
        finally:
            ld.close()

    def test_loader_state_rides_disk_commit(self, tmp_path, monkeypatch):
        """The participant protocol must survive the durable pickle
        path: commit in one 'incarnation', load in a fresh one."""
        from horovod_tpu import elastic

        monkeypatch.setenv("HVTPU_ELASTIC_STATE_DIR", str(tmp_path))
        ld = _make_loader(n=24, batch=4, seed=9)
        st = elastic.ObjectState(data=ld.state)
        it = iter(ld)
        first = [next(it)["y"].tolist() for _ in range(3)]
        st.save()
        st.wait_durable()
        ld.close()

        ld2 = _make_loader(n=24, batch=4, seed=9)
        st2 = elastic.ObjectState(data=ld2.state)
        try:
            import pickle

            from horovod_tpu.core import durable as core_durable

            seq = core_durable.latest_verified(str(tmp_path))
            assert seq is not None
            payload = core_durable.read_snapshot(
                str(tmp_path), seq)["state.pkl"]
            st2._from_disk_payload(pickle.loads(payload))
            assert ld2.state.cursor == 12 and ld2.state.seed == 9
            rest = [v for b in ld2 for v in b["y"].tolist()]
            flat = [v for b in first for v in b] + rest
            assert sorted(flat) == list(range(24))
        finally:
            ld2.close()

    def test_stream_crosses_epochs(self):
        ld = _make_loader(n=8, batch=4, seed=1)
        try:
            s = ld.stream()
            got = [next(s)["y"] for _ in range(5)]
            assert ld.state.epoch == 2
            assert all(len(g) == 4 for g in got)
        finally:
            ld.close()

    def test_with_indices_and_transform(self):
        calls = []
        ld = _make_loader(n=8, batch=4, with_indices=True,
                          transform=lambda b: calls.append(1) or b)
        try:
            idx, batch = next(iter(ld))
            assert np.array_equal(np.sort(idx), np.sort(batch["y"]))
            assert calls
        finally:
            ld.close()

    def test_debug_state_registered(self):
        from horovod_tpu.obs import metrics as obs_metrics

        ld = _make_loader(n=8, batch=4, name="dbg")
        try:
            next(iter(ld))
            snap = obs_metrics.debug_snapshot()
            assert "data" in snap
            entry = snap["data"]["dbg"]
            assert entry["samples"] == 8
            assert entry["delivered_batches"] >= 1
            assert entry["prefetch_alive"] is True
        finally:
            ld.close()

    def test_wait_metric_counts_batches(self):
        from horovod_tpu.obs import metrics as obs_metrics

        def wait_count():
            fam = obs_metrics.snapshot().get("hvtpu_data_wait_seconds")
            if not fam:
                return 0
            return sum(c["count"] for c in fam["values"].values())

        ld = _make_loader(n=8, batch=4)
        try:
            before = wait_count()
            list(ld)
            assert wait_count() - before == 2
        finally:
            ld.close()


# ---------------------------------------------------------------------------
# the producer's side: three counters and four spans at one set of boundaries
# ---------------------------------------------------------------------------

_STAGE_COUNTERS = ("hvtpu_data_fetch_seconds",
                   "hvtpu_data_transform_seconds",
                   "hvtpu_data_backpressure_seconds")


def _stage_totals():
    """``{counter: (sum, count)}`` of the loader's four histograms."""
    from horovod_tpu.obs import metrics as obs_metrics

    snap = obs_metrics.snapshot()
    out = {}
    for name in _STAGE_COUNTERS + ("hvtpu_data_wait_seconds",):
        cells = snap[name]["values"].values()
        out[name] = (sum(c["sum"] for c in cells),
                     sum(c["count"] for c in cells))
    return out


def _stage_deltas(before):
    after = _stage_totals()
    return {name: (after[name][0] - before[name][0],
                   after[name][1] - before[name][1]) for name in after}


def _stages_since(t):
    from horovod_tpu.data import loader as loader_mod

    return [s for s in loader_mod.recent_stages() if s[0] >= t]


def _busy_share(deltas):
    fetch, transform, parked = (deltas[name][0] for name in _STAGE_COUNTERS)
    return (fetch + transform) / (fetch + transform + parked)


class _SleepySource(ArraySource):
    def __init__(self, n, seconds):
        super().__init__({"y": np.arange(n)})
        self.seconds = seconds

    def fetch(self, indices):
        time.sleep(self.seconds)
        return super().fetch(indices)


class _FailingSource(ArraySource):
    """Raises on the ``fail_on``-th fetch."""

    def __init__(self, n, fail_on):
        super().__init__({"y": np.arange(n)})
        self.fail_on, self.fetches = fail_on, 0

    def fetch(self, indices):
        self.fetches += 1
        if self.fetches == self.fail_on:
            raise OSError("shard unreadable")
        return super().fetch(indices)


class TestProducerStages:
    def test_each_histogram_gains_one_observation_a_batch(self):
        before = _stage_totals()
        ld = _make_loader(n=40, batch=4)
        try:
            assert len(list(ld)) == 10
        finally:
            ld.close()      # the thread is joined: the counts stand still
        deltas = _stage_deltas(before)
        counts = {deltas[name][1] for name in _STAGE_COUNTERS}
        assert len(counts) == 1, deltas
        # every batch delivered was queued, and the producer runs at
        # most the queue's depth ahead of the consumer
        assert 10 <= counts.pop() <= 10 + ld.prefetch_depth
        assert deltas["hvtpu_data_wait_seconds"][1] == 10

    def test_slow_source_keeps_the_producer_busy(self):
        before = _stage_totals()
        ld = ElasticDataLoader(_SleepySource(40, 0.02), batch_size=4,
                               device_put=False)
        try:
            list(ld)
        finally:
            ld.close()
        deltas = _stage_deltas(before)
        assert _busy_share(deltas) > 0.9, deltas
        fetch_s, batches = deltas["hvtpu_data_fetch_seconds"]
        assert fetch_s / batches >= 0.02

    def test_slow_consumer_parks_the_producer(self):
        before = _stage_totals()
        ld = _make_loader(n=48, batch=4)
        slept = 0.0
        try:
            for _ in ld:
                t0 = time.perf_counter()
                time.sleep(0.03)
                slept += time.perf_counter() - t0
        finally:
            ld.close()
        deltas = _stage_deltas(before)
        assert _busy_share(deltas) < 0.2, deltas
        # the queue holds two and a third is parked when the consumer
        # first sleeps; each later sleep is one put's wait
        parked_s = deltas["hvtpu_data_backpressure_seconds"][0]
        assert 0.6 * slept <= parked_s <= slept + 0.05, (parked_s, slept)

    def test_a_fetch_that_raises_reaches_the_consumer_and_notes_nothing(self):
        before = _stage_totals()
        ld = ElasticDataLoader(_FailingSource(24, fail_on=3), batch_size=4,
                               device_put=False)
        try:
            it = iter(ld)
            next(it), next(it)
            with pytest.raises(RuntimeError, match="prefetch failed") as ei:
                next(it)
            assert isinstance(ei.value.__cause__, OSError)
        finally:
            ld.close()
        deltas = _stage_deltas(before)
        assert {deltas[name][1] for name in _STAGE_COUNTERS} == {2}

    def test_recent_stages_holds_what_the_counters_summed(self):
        t_before = time.perf_counter()
        before = _stage_totals()
        ld = _make_loader(n=24, batch=4)
        try:
            list(ld)
        finally:
            ld.close()
        deltas = _stage_deltas(before)
        stages = _stages_since(t_before)
        assert len(stages) == deltas["hvtpu_data_fetch_seconds"][1]
        assert all(t0 <= t1 <= t2 <= t3 <= time.perf_counter()
                   for t0, t1, t2, t3 in stages)
        for k, name in enumerate(_STAGE_COUNTERS):
            assert sum(s[k + 1] - s[k] for s in stages) == pytest.approx(
                deltas[name][0], abs=1e-9)

    def test_a_profile_holds_the_four_spans_and_they_match_the_counters(
            self, tmp_path):
        import glob

        import jax
        from jax.profiler import ProfileData

        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0     # the benchmark's options
        options.host_tracer_level = 1
        t_before = time.perf_counter()
        before = _stage_totals()
        jax.profiler.start_trace(str(tmp_path), profiler_options=options)
        try:
            ld = ElasticDataLoader(
                _SleepySource(24, 0.004), batch_size=4, device_put=False,
                transform=lambda b: (time.sleep(0.002), b)[1])
            try:
                for _ in ld:
                    time.sleep(0.01)
            finally:
                ld.close()
        finally:
            jax.profiler.stop_trace()
        deltas = _stage_deltas(before)
        stages = _stages_since(t_before)
        (path,) = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
        lines = {}   # one line of the host plane per thread
        for plane in ProfileData.from_file(path).planes:
            if not plane.name.startswith("/host:"):
                continue
            for k, line in enumerate(plane.lines):
                for e in line.events:
                    if e.name.startswith("hvtpu:loader."):
                        lines.setdefault(k, {}).setdefault(
                            e.name[len("hvtpu:loader."):], []).append(
                                (e.start_ns, e.duration_ns / 1e9))
        assert sorted(map(sorted, lines.values())) == [
            ["fetch", "put", "transform"], ["wait"]]
        producer, consumer = sorted(lines.values(), key=len, reverse=True)
        # a batch parked when the loader closed has spans and no
        # observation; every other span is one observation's length
        for k, stage in enumerate(("fetch", "transform", "put")):
            spans = [s for _, s in sorted(producer[stage])]
            assert len(stages) <= len(spans) <= len(stages) + 1
            for span_s, s in zip(spans, stages):
                assert span_s == pytest.approx(s[k + 1] - s[k], abs=1e-3)
        waits = [s for _, s in consumer["wait"]]
        wait_s, wait_n = deltas["hvtpu_data_wait_seconds"]
        assert len(waits) == wait_n == 6
        assert sum(waits) == pytest.approx(wait_s, abs=wait_n * 1e-3)

    def test_outside_a_profiler_session_a_span_writes_nothing(self, tmp_path):
        from horovod_tpu.obs import tracing

        assert tracing.ACTIVE is False and tracing.get_tracer() is None
        with tracing.span("loader.fetch"):
            pass
        assert tracing.ACTIVE is False and tracing.get_tracer() is None
        # nor into the program's own trace, which HVTPU_TRACE switches
        tracing.install(str(tmp_path))
        try:
            with tracing.span("loader.fetch"):
                assert tracing.ACTIVE is True
        finally:
            tracing.uninstall()
        (trace,) = tmp_path.iterdir()
        assert "loader.fetch" not in trace.read_text()
        assert not list(tmp_path.glob("plugins"))


# ---------------------------------------------------------------------------
# fault site
# ---------------------------------------------------------------------------

class TestDataFaultSite:
    def test_grammar_accepts_data_next(self):
        cs = faults.parse_spec(
            "data.next:delay(50)@rank=1;data.next:drop;data.next:error")
        assert [c.site for c in cs] == ["data.next"] * 3

    def test_delay_stalls_delivery(self):
        faults.install("data.next:delay(120)@times=1", rank=0)
        ld = _make_loader(n=8, batch=4)
        try:
            t0 = time.perf_counter()
            next(iter(ld))
            assert time.perf_counter() - t0 >= 0.12
        finally:
            ld.close()

    def test_drop_loses_one_batch_and_advances_cursor(self):
        faults.install("data.next:drop@times=1", rank=0)
        ld = _make_loader(n=12, batch=4, seed=4)
        try:
            seen = [v for b in ld for v in b["y"].tolist()]
            # one injected drop: 4 of 12 samples lost, none repeated
            assert len(seen) == 8 and len(set(seen)) == 8
            assert ld.state.epoch == 1 and ld.state.cursor == 0
        finally:
            ld.close()

    def test_error_raises_injected_fault(self):
        faults.install("data.next:error@times=1", rank=0)
        ld = _make_loader(n=8, batch=4)
        try:
            with pytest.raises(faults.InjectedFault):
                next(iter(ld))
        finally:
            ld.close()


# ---------------------------------------------------------------------------
# acceptance: 2-proc elastic exactly-once + trace attribution
# ---------------------------------------------------------------------------

_DELIVER_RE = re.compile(
    r"DELIVER rank=(\d+) size=(\d+) gen=(\d+) epoch=(\d+) "
    r"idx=\[([0-9, ]*)\]")


def _launch_data_elastic(tmp_path, fault_spec, epochs=2, samples=48,
                         batch=4, timeout=300):
    from conftest import make_discovery_script

    _hosts, disc = make_discovery_script(tmp_path, "localhost:2")
    env = dict(os.environ)
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["ELASTIC_EPOCHS"] = str(epochs)
    env["DATA_SAMPLES"] = str(samples)
    env["DATA_BATCH"] = str(batch)
    env["EPOCH_SLEEP"] = "0.3"
    env["HVTPU_ELASTIC_DISCOVERY_INTERVAL"] = "0.2"
    cmd = [
        sys.executable, "-m", "horovod_tpu.runner",
        "--host-discovery-script", disc,
        "--min-np", "2", "--cpu-devices", "1", "--verbose",
        "--fault-spec", fault_spec,
        "--", sys.executable, _SCRIPT,
    ]
    res = subprocess.run(cmd, env=env, cwd=_REPO, timeout=timeout,
                         capture_output=True, text=True)
    return res, res.stdout + res.stderr


@pytest.mark.multiprocess
@pytest.mark.slow
def test_preempt_mid_epoch_delivers_each_sample_exactly_once(tmp_path):
    """ISSUE-9 acceptance: rank 1 is preempted at its 3rd per-batch
    commit (mid-epoch).  The drain commits the loader cursor, the
    driver resizes 2->2, and across both incarnations every sample
    index of every epoch is delivered exactly once — no repeats from
    restarting the epoch, no drops from the in-flight prefetch."""
    epochs, samples = 2, 48
    res, out = _launch_data_elastic(
        tmp_path, "worker.step:preempt@rank=1,count=3", epochs=epochs,
        samples=samples)
    assert res.returncode == 0, out[-4000:]
    assert "exiting 79 for a planned departure" in out, out[-4000:]
    assert out.count("launching 2 workers") == 2, out[-4000:]
    assert f"DONE size=2 epoch={epochs}" in out, out[-4000:]
    per_epoch = {e: [] for e in range(epochs)}
    gens = set()
    for m in _DELIVER_RE.finditer(out):
        gens.add(int(m.group(3)))
        idx = [int(v) for v in m.group(5).split(",") if v.strip()]
        per_epoch[int(m.group(4))].extend(idx)
    assert gens == {0, 1}, (gens, out[-4000:])
    for e in range(epochs):
        got = sorted(per_epoch[e])
        assert got == list(range(samples)), (
            f"epoch {e}: delivered {len(got)} samples "
            f"({len(set(got))} unique) — exactly-once violated")


@pytest.mark.multiprocess
@pytest.mark.slow
def test_trace_attributes_data_delay_to_input_phase(tmp_path):
    """ISSUE-9 acceptance: an injected ``data.next:delay`` on rank 1
    must show up in ``hvtputrace report`` as INPUT wait (data_wait) on
    rank 1 — not as compute, and bigger than rank 0's."""
    from horovod_tpu.runner import run as run_fn
    from tools import hvtputrace

    trace_dir = str(tmp_path / "traces")
    os.makedirs(trace_dir, exist_ok=True)

    def body():
        import numpy as _np

        import horovod_tpu as _hvt
        from horovod_tpu.data import ArraySource as _AS
        from horovod_tpu.data import ElasticDataLoader as _EDL

        _hvt.init()
        ld = _EDL(_AS({"y": _np.arange(32)}), batch_size=4,
                  device_put=False, name="traced")
        for _ in ld:
            pass
        ld.close()
        _hvt.shutdown()
        return "ok"

    env = {
        "PYTHONPATH": _REPO + os.pathsep + os.environ.get(
            "PYTHONPATH", ""),
        "HVTPU_TRACE": trace_dir,
        "HVTPU_FAULT_SPEC": "data.next:delay(80)@rank=1,times=3",
    }
    assert run_fn(body, np=2, cpu_devices=1, env=env,
                  start_timeout=300.0) == ["ok", "ok"]

    rep = hvtputrace.report(trace_dir)
    r0, r1 = rep["per_rank"][0], rep["per_rank"][1]
    # three 80 ms delays land inside rank 1's DATA_WAIT spans
    assert r1["data_wait_us"] > 200_000, rep["per_rank"]
    assert r1["data_wait_us"] > 4 * r0["data_wait_us"], rep["per_rank"]
    assert r1["data_wait_fraction"] > 0, rep["per_rank"]
    # the rendered report surfaces the input column
    text = hvtputrace.render_report(rep)
    assert "input_ms" in text and "input%" in text
