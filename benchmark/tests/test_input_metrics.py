"""The three ``input`` metrics: what they make of a hand-made run, that
they take the untraced window's batches alone, and that with a program
that keeps no such record (the parent commit) they read nothing and do
not raise."""

import sys
import time

import numpy as np
import pytest

from benchmark import cells, producer_stages
from benchmark.loop import Window
from benchmark.observations import Observations

NAMES = ("input_fetch_ms_per_batch", "input_transform_ms_per_batch",
         "input_producer_busy_share")


def observations(first_dispatch, last_completion):
    window = Window(first_dispatch=first_dispatch,
                    last_completion=last_completion)
    return Observations(
        config={}, traffic={}, chips=1, device_kind="cpu", window=window,
        setup_s=0.0, samples_per_step_per_chip=1, train_flops_per_sample=1,
        compile_s=0.0, cache_hits=0, gradient_bytes=0,
        memory_peak_bytes=None)


def read_all(obs):
    return {name: cells.load_metric("per_layer", name).read(obs)
            for name in NAMES}


def batch(t0, fetch_ms, transform_ms, parked_ms):
    t1 = t0 + fetch_ms / 1e3
    t2 = t1 + transform_ms / 1e3
    return (t0, t1, t2, t2 + parked_ms / 1e3)


@pytest.fixture
def stages(monkeypatch):
    """The program's record, replaced by a list the test fills."""
    from horovod_tpu.data import loader

    record = []
    monkeypatch.setattr(loader, "_STAGES", record)
    return record


def test_the_readers_on_a_hand_made_window(stages):
    # set-up: a batch parked for two seconds before the window opens
    stages.append(batch(95.0, 80.0, 5.0, 2000.0))
    # the window, 100 s to 110 s: 80 + 4 ms of work, 16 ms parked, and
    # one batch of 90 + 6 ms that was never parked
    stages.extend(batch(100.0 + 0.1 * k, 80.0, 4.0, 16.0)
                  for k in range(10))
    stages.append(batch(101.5, 90.0, 6.0, 0.0))
    # the traced window after it: the profiler's stall lands in the put
    stages.append(batch(111.0, 80.0, 4.0, 2800.0))
    # a batch the window's end cut through
    stages.append(batch(109.95, 80.0, 4.0, 100.0))
    got = read_all(observations(100.0, 110.0))
    assert got["input_fetch_ms_per_batch"] == pytest.approx(890.0 / 11)
    assert got["input_transform_ms_per_batch"] == pytest.approx(46.0 / 11)
    assert got["input_producer_busy_share"] == pytest.approx(
        100.0 * 936.0 / (936.0 + 160.0))
    seen = producer_stages.over_window(observations(100.0, 110.0))
    assert set(seen) == set(producer_stages.COUNTERS)
    assert {c["count"] for c in seen.values()} == {11}


def test_a_producer_that_is_never_parked_reads_100(stages):
    stages.extend(batch(float(k), 900.0, 100.0, 0.0) for k in range(5))
    got = read_all(observations(0.0, 10.0))
    assert got["input_producer_busy_share"] == 100.0
    assert got["input_fetch_ms_per_batch"] == pytest.approx(900.0)


def test_no_batch_in_the_window_reads_nothing(stages):
    stages.append(batch(5.0, 1.0, 1.0, 1.0))
    assert read_all(observations(100.0, 110.0)) == dict.fromkeys(NAMES)


def test_a_program_without_the_record_reads_nothing(monkeypatch):
    from horovod_tpu.data import loader

    monkeypatch.delattr(loader, "recent_stages")
    assert read_all(observations(0.0, 1e12)) == dict.fromkeys(NAMES)
    # nor without the program's loader at all
    monkeypatch.setitem(sys.modules, "horovod_tpu.data.loader", None)
    assert read_all(observations(0.0, 1e12)) == dict.fromkeys(NAMES)


def test_a_real_loader_read_over_a_window_of_its_own():
    """A consumer that sleeps parks the producer; the batches queued
    before the window opened stay out of it."""
    from horovod_tpu.data import ArraySource, ElasticDataLoader

    loader = ElasticDataLoader(ArraySource({"y": np.arange(64)}),
                               batch_size=4, device_put=False)
    try:
        batches = loader.stream()
        next(batches)
        time.sleep(0.3)        # set-up: the producer parks meanwhile
        first = time.perf_counter()
        for _ in range(10):
            next(batches)
            time.sleep(0.02)
        last = time.perf_counter()
    finally:
        loader.close()
    obs = observations(first, last)
    seen = producer_stages.over_window(obs)
    parked = seen["hvtpu_data_backpressure_seconds"]
    assert 7 <= parked["count"] <= 10
    assert parked["sum"] < 0.25       # the 0.3 s of set-up is not in it
    got = read_all(obs)
    assert 0.0 < got["input_producer_busy_share"] < 20.0
    assert 0.0 < got["input_fetch_ms_per_batch"] < 5.0
