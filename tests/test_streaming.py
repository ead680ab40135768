"""Streaming generators (num_returns="streaming") + streaming Data reads.

Reference semantics being matched: ObjectRefGenerator / generator_waiter.h
(python/ray/_raylet.pyx) — refs are yielded in order as the task produces
them, errors re-raise at the failure position, and Data consumes read
streams so the first block arrives before the last file is read.
"""

import time

import pytest

import ray_tpu


@pytest.fixture(scope="module", autouse=True)
def ray():
    ray_tpu.init(num_cpus=4, ignore_reinit_error=True)
    yield ray_tpu
    ray_tpu.shutdown()


def test_stream_basic_order():
    @ray_tpu.remote(num_returns="streaming")
    def gen(n):
        for i in range(n):
            yield i * 10

    g = gen.remote(8)
    assert isinstance(g, ray_tpu.ObjectRefGenerator)
    assert [ray_tpu.get(r) for r in g] == [i * 10 for i in range(8)]


def test_stream_incremental_arrival():
    """The first yield is consumable while the producer still runs."""

    @ray_tpu.remote(num_returns="streaming")
    def slowgen():
        for i in range(3):
            yield i
            time.sleep(0.8)

    g = slowgen.remote()
    t0 = time.monotonic()
    assert ray_tpu.get(next(g)) == 0
    assert time.monotonic() - t0 < 0.7  # producer needs ~2.4s total
    assert [ray_tpu.get(r) for r in g] == [1, 2]


def test_stream_empty():
    @ray_tpu.remote(num_returns="streaming")
    def empty():
        return iter(())

    assert list(empty.remote()) == []


def test_stream_error_after_items():
    """Items yielded before the failure stay consumable; the error
    re-raises at the failure position."""

    @ray_tpu.remote(num_returns="streaming", max_retries=0)
    def bad():
        yield "ok"
        raise ValueError("boom")

    g = bad.remote()
    assert ray_tpu.get(next(g)) == "ok"
    with pytest.raises(ray_tpu.exceptions.RayTaskError):
        next(g)


def test_stream_large_items():
    """Items above the inline cap go through the object store."""
    import numpy as np

    @ray_tpu.remote(num_returns="streaming")
    def bigs():
        for i in range(3):
            yield np.full(300_000, i, dtype=np.float64)

    sums = [float(ray_tpu.get(r).sum()) for r in bigs.remote()]
    assert sums == [0.0, 300_000.0, 600_000.0]


def test_stream_non_generator_errors():
    @ray_tpu.remote(num_returns="streaming", max_retries=0)
    def notgen():
        return 42

    g = notgen.remote()
    with pytest.raises(ray_tpu.exceptions.RayTaskError):
        next(g)


def test_stream_next_timeout():
    @ray_tpu.remote(num_returns="streaming")
    def stuck():
        time.sleep(5)
        yield 1

    g = stuck.remote()
    with pytest.raises(ray_tpu.exceptions.GetTimeoutError):
        g.next(timeout=0.3)
    # ... and the stream still works afterwards.
    assert ray_tpu.get(g.next(timeout=30)) == 1


def test_stream_raylet_mediated_path():
    """Non-DEFAULT scheduling strategies bypass direct submission — no
    stream_item pushes exist, so the generator must fall back to probing
    the object directory."""

    @ray_tpu.remote(num_returns="streaming", scheduling_strategy="SPREAD")
    def gen(n):
        for i in range(n):
            yield i + 100

    assert [ray_tpu.get(r) for r in gen.remote(4)] == [100, 101, 102, 103]


def test_actor_streaming_method():
    @ray_tpu.remote
    class Counter:
        def countdown(self, n):
            while n:
                yield n
                n -= 1

    c = Counter.remote()
    g = c.countdown.options(num_returns="streaming").remote(4)
    assert [ray_tpu.get(r) for r in g] == [4, 3, 2, 1]
    ray_tpu.kill(c)


def test_async_actor_streaming_method():
    @ray_tpu.remote
    class AsyncGen:
        async def agen(self, n):
            import asyncio

            for i in range(n):
                await asyncio.sleep(0.01)
                yield i * 2

    a = AsyncGen.remote()
    g = a.agen.options(num_returns="streaming").remote(3)
    assert [ray_tpu.get(r) for r in g] == [0, 2, 4]
    ray_tpu.kill(a)


def test_data_streaming_read_first_block_early(tmp_path):
    """A Data read over a slow multi-block datasource delivers the first
    batch before the datasource finishes producing: before it has
    produced its third block, which it holds back until the consumer
    says it has the first batch (or, for a read that does not stream,
    until it has waited long enough for the test to fail and not hang).
    What is compared is what the source did, not a number of seconds."""
    import numpy as np
    import pyarrow as pa

    from ray_tpu.data.block import BlockMetadata
    from ray_tpu.data.datasource import Datasource, ReadTask

    produced, got_first = str(tmp_path / "produced"), str(tmp_path / "got_first")

    class SlowSource(Datasource):
        def get_read_tasks(self, parallelism):
            def read():
                import os

                from ray_tpu._private import retry

                for i in range(4):
                    if i == 2:  # later "files" are slow
                        wait = retry.POLL.start(deadline_s=30)
                        while not os.path.exists(got_first):
                            delay = wait.next_delay()
                            if delay is None:
                                break
                            time.sleep(delay)
                    with open(produced, "a") as f:
                        f.write(f"{i}\n")
                    yield pa.table({"x": np.full(10, i)})

            meta = BlockMetadata(num_rows=40, size_bytes=40 * 8, schema=None, input_files=None)
            return [ReadTask(read, meta)]

    import ray_tpu.data as rd

    ds = rd.read_datasource(SlowSource(), parallelism=1)
    it = ds.iter_batches(batch_size=10)
    first = next(iter(it))
    with open(produced) as f:
        blocks = len(f.read().split())
    open(got_first, "w").close()
    assert len(first["x"]) == 10
    assert blocks < 3, f"the source had produced {blocks} blocks before the first batch came — read is not streaming"
