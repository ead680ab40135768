"""Native shared-memory arena store: unit tests for the C++ allocator /
index / eviction + cluster integration (reference test model:
src/ray/object_manager/plasma/ C++ tests + python/ray/tests/test_object_store.py).
"""

import os

import numpy as np
import pytest

from ray_tpu._native.arena import NativeArena


@pytest.fixture
def arena(tmp_path):
    path = "/dev/shm/test_arena_%d" % os.getpid()
    if os.path.exists(path):
        os.unlink(path)
    a = NativeArena.create(path, 1 << 20)
    assert a is not None
    yield a
    a.close()
    os.unlink(path)


def test_alloc_seal_lookup_roundtrip(arena):
    buf = arena.alloc(b"id1", 64)
    buf[:11] = b"hello arena"
    del buf
    assert arena.seal(b"id1")
    arena.release_create(b"id1")  # drop creator ref (held from alloc)
    v = arena.lookup(b"id1")
    assert bytes(v[:11]) == b"hello arena" and len(v) == 64
    del v
    arena.decref(b"id1")


def test_unsealed_not_visible(arena):
    arena.alloc(b"id2", 10)
    assert not arena.contains(b"id2")
    assert arena.lookup(b"id2") is None
    arena.seal(b"id2")
    assert arena.contains(b"id2")


def test_duplicate_alloc_rejected(arena):
    arena.alloc(b"dup", 10)
    code, view = arena.alloc_status(b"dup", 10)
    assert code == -2 and view is None


def test_refcount_blocks_delete_and_eviction(arena):
    buf = arena.alloc(b"pinned", 500_000)
    del buf
    arena.seal(b"pinned")
    arena.release_create(b"pinned")
    v = arena.lookup(b"pinned")  # refcount 1
    assert not arena.delete(b"pinned")
    # eviction cannot reclaim it either: a too-big request must fail
    assert arena.evict_lru(900_000) is None
    del v
    arena.decref(b"pinned")
    assert arena.delete(b"pinned")


def test_free_space_reuse_and_coalescing(arena):
    for i in range(4):
        arena.alloc(b"b%d" % i, 200_000)
        arena.seal(b"b%d" % i)
        arena.release_create(b"b%d" % i)
    used_before = arena.used
    # delete middle neighbours -> coalesced 400k hole fits one 390k object
    assert arena.delete(b"b1")
    assert arena.delete(b"b2")
    buf = arena.alloc(b"big", 390_000)
    assert buf is not None
    assert arena.used == used_before - 2 * 200_000 + 390_000


def test_lru_eviction_order(arena):
    import time

    for i in range(5):
        arena.alloc(b"e%d" % i, 150_000)
        arena.seal(b"e%d" % i)
        arena.release_create(b"e%d" % i)
        time.sleep(0.002)
    # touch e0 so it becomes most-recently-used
    v = arena.lookup(b"e0")
    del v
    arena.decref(b"e0")
    evicted = arena.evict_lru(300_000)
    assert evicted is not None
    evicted_ids = {e[:2] for e in evicted}
    assert b"e0" not in evicted_ids  # the touched object survived
    assert b"e1" in evicted_ids  # the coldest went first


def test_attach_sees_other_process_writes(arena, tmp_path):
    import subprocess
    import sys

    path = "/dev/shm/test_arena_%d" % os.getpid()
    code = f"""
import sys
sys.path.insert(0, {repr(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))})
from ray_tpu._native.arena import NativeArena
a = NativeArena.attach({path!r})
buf = a.alloc(b"xproc", 32)
buf[:7] = b"fromsub"
del buf
a.seal(b"xproc")
a.release_create(b"xproc")
a.close()
"""
    subprocess.run([sys.executable, "-c", code], check=True, timeout=60)
    v = arena.lookup(b"xproc")
    assert v is not None and bytes(v[:7]) == b"fromsub"
    del v
    arena.decref(b"xproc")


def test_cluster_large_object_via_arena(ray_cluster):
    import ray_tpu

    w = ray_tpu._private.worker.get_global_worker()
    if w.store.arena is None:
        pytest.skip("arena unavailable in this cluster")
    arr = np.random.default_rng(0).normal(size=(512, 512))  # 2MB
    ref = ray_tpu.put(arr)

    @ray_tpu.remote
    def total(a):
        return float(a.sum())

    assert ray_tpu.get(total.remote(ref)) == pytest.approx(float(arr.sum()))
    out = ray_tpu.get(ref)
    np.testing.assert_array_equal(out, arr)


def test_eownerdead_repair(arena, tmp_path):
    """A client dying inside the critical section (mid-mutation) must not
    corrupt the arena: the next locker repairs the index/allocator from
    the sealed entries (reference: plasma store survives client death;
    here via robust-mutex EOWNERDEAD + repair pass)."""
    import subprocess
    import sys

    # A sealed object that must survive the repair.
    buf = arena.alloc(b"survivor", 128)
    buf[:4] = b"keep"
    del buf
    arena.seal(b"survivor")
    arena.release_create(b"survivor")
    path = "/dev/shm/test_arena_%d" % os.getpid()
    # Child: allocate WITHOUT sealing (mid-write garbage), grab the arena
    # mutex, and die holding it.
    code = f"""
import os
from ray_tpu._native.arena import NativeArena
a = NativeArena.attach({path!r})
buf = a.alloc(b"halfwritten", 256)
buf[:4] = b"junk"
del buf
a._test_lock_and_abandon()
os._exit(42)
"""
    proc = subprocess.run([sys.executable, "-c", code], timeout=60)
    # 42 proves the child really reached lock-and-abandon (a crash before
    # that would make the assertions below pass vacuously).
    assert proc.returncode == 42
    # Next lock observes EOWNERDEAD and repairs: the sealed object is
    # intact, the mid-write entry is gone, and allocation still works.
    v = arena.lookup(b"survivor")
    assert v is not None and bytes(v[:4]) == b"keep"
    del v
    arena.decref(b"survivor")
    assert not arena.contains(b"halfwritten")
    assert arena.num_objects == 1
    buf = arena.alloc(b"after", 64)
    buf[:2] = b"ok"
    del buf
    assert arena.seal(b"after")
    arena.release_create(b"after")
    assert arena.contains(b"after")
