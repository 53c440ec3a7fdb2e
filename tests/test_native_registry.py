"""Native C++ interning registry: behavioral parity with the Python
Registry (differential test over random op sequences), thread safety, and
the batch FFI path. Skipped when g++/the .so is unavailable — the factory
then falls back to Python transparently."""

import random
import threading

import pytest

from sentinel_tpu.core.registry import Registry, make_registry

native = pytest.importorskip("sentinel_tpu.native")
if not native.native_available():
    pytest.skip("native library unavailable", allow_module_level=True)

from sentinel_tpu.native import NativeRegistry  # noqa: E402


def test_factory_returns_native():
    assert isinstance(make_registry(16), NativeRegistry)


def _outcome(fn):
    """Result or the all-pinned overflow marker — both impls must agree."""
    try:
        return fn()
    except RuntimeError:
        return "ALL_PINNED"


def test_differential_vs_python_registry():
    """Same op sequence → identical ids, evictions, lengths, lookups,
    and identical all-pinned overflow errors."""
    rng = random.Random(42)
    names = [f"res-{i}" for i in range(40)]
    py = Registry(16, reserved=("__r__",))
    nat = NativeRegistry(16, reserved=("__r__",))
    for step in range(3000):
        op = rng.random()
        name = rng.choice(names)
        if op < 0.55:
            assert (_outcome(lambda: py.get_or_create(name))
                    == _outcome(lambda: nat.get_or_create(name))), step
        elif op < 0.70:
            assert py.lookup(name) == nat.lookup(name), step
        elif op < 0.80:
            assert (_outcome(lambda: py.pin(name))
                    == _outcome(lambda: nat.pin(name))), step
        elif op < 0.90:
            py.unpin(name)
            nat.unpin(name)
        else:
            assert sorted(py.drain_evicted()) == sorted(nat.drain_evicted()), step
        assert len(py) == len(nat), step
    assert sorted(py.items()) == sorted(nat.items())


def test_name_of_and_capacity_guard():
    r = NativeRegistry(4)
    rid = r.get_or_create("hello")
    assert r.name_of(rid) == "hello"
    assert r.name_of(99) is None
    assert r.name_of(-1) is None


def test_all_pinned_overflow_raises():
    r = NativeRegistry(3)
    for n in ("a", "b", "c"):
        r.pin(n)
    with pytest.raises(RuntimeError):
        r.get_or_create("overflow")


def test_batch_matches_scalar_path():
    r1 = NativeRegistry(64)
    r2 = NativeRegistry(64)
    names = [f"n{i % 10}" for i in range(50)]
    ids_batch = r1.get_or_create_batch(names)
    ids_scalar = [r2.get_or_create(n) for n in names]
    assert ids_batch.tolist() == ids_scalar


def test_unicode_names():
    r = NativeRegistry(8)
    rid = r.get_or_create("ресурс-例")
    assert r.lookup("ресурс-例") == rid
    assert r.name_of(rid) == "ресурс-例"


def test_thread_safety_no_duplicate_ids():
    r = NativeRegistry(256)
    results = [None] * 8

    def work(t):
        local = {}
        for i in range(2000):
            name = f"shared-{i % 100}"
            local[name] = r.get_or_create(name)
        results[t] = local

    threads = [threading.Thread(target=work, args=(t,)) for t in range(8)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    # all threads agree on every name's id (no duplicate allocation)
    for name in results[0]:
        ids = {results[t][name] for t in range(8)}
        assert len(ids) == 1, name


def test_eviction_reuses_rows_and_reports_them():
    r = NativeRegistry(4, reserved=("keep",))
    first = [r.get_or_create(f"x{i}") for i in range(3)]
    assert len(set(first)) == 3
    r.get_or_create("x0")            # touch → LRU is x1
    rid = r.get_or_create("new")
    assert rid == first[1]           # x1's row recycled
    assert r.drain_evicted() == [first[1]]
    assert r.lookup("keep") is not None   # pinned reserved row untouched


def test_very_long_names_roundtrip():
    r = NativeRegistry(4)
    long_name = "я" * 5000            # 10k UTF-8 bytes, > the 4096 buffer
    rid = r.get_or_create(long_name)
    assert r.name_of(rid) == long_name
    assert dict(r.items())[long_name] == rid


def test_items_is_linear_in_the_table():
    """At the 1M-row product geometry ``items()`` runs on every telemetry
    drain. It used to re-copy the whole name buffer per entry (ctypes
    ``.raw`` inside the loop) — quadratic: minutes at 100k names, never
    finishing at 1M. Linear is a fraction of a second here."""
    import time
    n = 100_000
    r = NativeRegistry(n)
    r.get_or_create_batch([f"resource-name-{i}" for i in range(n)])
    t0 = time.perf_counter()
    items = r.items()
    assert time.perf_counter() - t0 < 10.0
    assert len(items) == n and len(dict(items)) == n
