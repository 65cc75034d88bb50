"""Input-pipeline telemetry (PR 4) + the reader error-propagation
satellites: buffered()'s swallowed producer exception, xmap_readers()'s
hanging consumer on a raising mapper (ordered AND unordered), queue
depth/wait instruments, and the feed-build -> boundedness wiring."""

import threading

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import flags, layers, monitor
from paddle_tpu.data_feeder import DataFeeder
from paddle_tpu.reader import buffered, xmap_readers
from paddle_tpu.reader.pipeline import DeviceLoader


@pytest.fixture(autouse=True)
def _clean_telemetry():
    flags.set_flags({"telemetry": False})
    yield
    flags.set_flags({"telemetry": False})


def _consume(gen_fn, timeout=10.0):
    """Drain a reader on a worker thread with a deadline: propagation
    must be BOUNDED — a hang is the regression these tests pin down."""
    out = {"items": [], "exc": None}

    def run():
        try:
            for x in gen_fn():
                out["items"].append(x)
        except BaseException as e:
            out["exc"] = e

    t = threading.Thread(target=run, daemon=True)
    t.start()
    t.join(timeout)
    assert not t.is_alive(), "reader hung instead of propagating"
    return out


class _Boom(Exception):
    pass


# --------------------------------------------------------------------------
# buffered(): producer exceptions reach the consumer (satellite)
# --------------------------------------------------------------------------

def test_buffered_propagates_producer_exception():
    def bad_reader():
        yield 1
        yield 2
        raise _Boom("producer died")

    out = _consume(buffered(bad_reader, size=4))
    assert out["items"] == [1, 2]  # items before the failure still flow
    assert isinstance(out["exc"], _Boom)


def test_buffered_happy_path_unchanged():
    out = _consume(buffered(lambda: iter(range(20)), size=3))
    assert out["items"] == list(range(20))
    assert out["exc"] is None


def test_buffered_error_propagates_with_full_queue():
    """The failure mode behind the bug: a producer that dies while the
    consumer is slow must still surface, not truncate the epoch."""
    def bad_reader():
        yield from range(8)
        raise _Boom("late death")

    out = _consume(buffered(bad_reader, size=2))
    assert out["items"] == list(range(8))
    assert isinstance(out["exc"], _Boom)


# --------------------------------------------------------------------------
# xmap_readers(): raising mappers propagate in both modes (satellite)
# --------------------------------------------------------------------------

def _mapper(x):
    if x == 5:
        raise _Boom(f"mapper choked on {x}")
    return x * 10


@pytest.mark.parametrize("order", [False, True])
def test_xmap_raising_mapper_propagates(order):
    reader = xmap_readers(_mapper, lambda: iter(range(10)),
                          process_num=2, buffer_size=4, order=order)
    out = _consume(reader)
    assert isinstance(out["exc"], _Boom)
    # unordered mode may deliver some mapped samples first; none of
    # them can be the poisoned one
    assert 50 not in out["items"]


@pytest.mark.parametrize("order", [False, True])
def test_xmap_happy_path(order):
    reader = xmap_readers(lambda x: x * 2, lambda: iter(range(16)),
                          process_num=4, buffer_size=4, order=order)
    out = _consume(reader)
    assert out["exc"] is None
    expected = [x * 2 for x in range(16)]
    assert (out["items"] == expected if order
            else sorted(out["items"]) == expected)


def test_xmap_source_reader_error_propagates():
    def bad_source():
        yield 1
        raise _Boom("source died")

    reader = xmap_readers(lambda x: x, bad_source,
                          process_num=2, buffer_size=4)
    out = _consume(reader)
    assert isinstance(out["exc"], _Boom)


# --------------------------------------------------------------------------
# queue depth + wait instruments
# --------------------------------------------------------------------------

def test_buffered_feeds_queue_instruments():
    monitor.enable()
    out = _consume(buffered(lambda: iter(range(10)), size=4))
    assert out["items"] == list(range(10))
    h = monitor.histogram("pt_reader_wait_seconds")
    assert h.count(labels={"site": "buffered", "role": "consumer"}) == 11
    assert h.count(labels={"site": "buffered", "role": "producer"}) == 10
    # depth gauge has a cell for the site (last observed depth)
    g = monitor.gauge("pt_reader_queue_depth")
    assert ("site", "buffered") in [
        kv for key in g._cells for kv in key]


def test_device_loader_consumer_wait_counts_as_input_wait():
    monitor.enable()
    loader = DeviceLoader(
        lambda: iter([{"x": np.ones((2, 4), np.float32)}] * 3),
        feed_names=["x"], depth=2)
    batches = list(loader)
    assert len(batches) == 3
    h = monitor.histogram("pt_reader_wait_seconds")
    waits = h.count(labels={"site": "device_loader", "role": "consumer"})
    assert waits == 4  # 3 batches + the END marker
    # consumer waits accumulated toward the verdict: a step recorded now
    # sees a nonzero input score
    monitor.record_step_phases(0.0, 0.0, 0.0, 0.0)
    assert monitor.boundedness()["shares"]["input"] == pytest.approx(1.0)


def test_data_feeder_build_time_observed():
    monitor.enable()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", shape=[4], dtype="float32")
    feeder = DataFeeder([x])
    batch = feeder.feed([(np.ones(4, np.float32),)] * 8)
    assert batch["x"].shape == (8, 4)
    assert monitor.histogram("pt_feed_build_seconds").count() == 1
    # disabled: no observation, identical output
    flags.set_flags({"telemetry": False})
    batch2 = feeder.feed([(np.ones(4, np.float32),)] * 8)
    np.testing.assert_array_equal(batch["x"], batch2["x"])
    assert monitor.histogram("pt_feed_build_seconds").count() == 1


def test_reader_instruments_silent_when_disabled():
    assert not monitor.enabled()
    out = _consume(buffered(lambda: iter(range(5)), size=2))
    assert out["items"] == list(range(5))
    assert monitor.histogram("pt_reader_wait_seconds")._cells == {}
    assert monitor.gauge("pt_reader_queue_depth")._cells == {}
