"""Spill framework tests (RapidsDeviceMemoryStoreSuite/
RapidsBufferCatalogSuite miniature: tiny budgets, temp dirs, real tiers)."""

import numpy as np
import pytest

from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.memory.coalesce import (
    RequireSingleBatch, TargetSize, coalesce_iterator)
from spark_rapids_tpu.memory.spill import (
    DEVICE, DISK, HOST, SpillableBatchCatalog, TpuSemaphore)


def make_batch(n=1000, seed=0):
    rng = np.random.default_rng(seed)
    return ColumnarBatch.from_pydict({
        "a": rng.integers(0, 100, n),
        "s": [f"row-{i}" for i in range(n)],
    })


def test_register_and_materialize_device(tmp_path):
    cat = SpillableBatchCatalog(device_budget=1 << 30,
                                spill_dir=str(tmp_path))
    b = make_batch()
    h = cat.register(b)
    assert h.tier == DEVICE
    out = h.materialize()
    assert out.to_pydict() == b.to_pydict()
    h.close()
    assert cat.stats()["num_handles"] == 0


def test_spill_to_host_and_back(tmp_path):
    b = make_batch()
    size = b.device_size_bytes()
    cat = SpillableBatchCatalog(device_budget=size + 100,
                                host_budget=1 << 30,
                                spill_dir=str(tmp_path))
    h1 = cat.register(b)
    h2 = cat.register(make_batch(seed=1))  # pushes h1 over budget
    assert h1.tier == HOST  # lowest priority (same) spilled first by id
    assert h2.tier == DEVICE
    assert cat.spilled_to_host_total > 0
    out = h1.materialize()  # unspills
    assert h1.tier == DEVICE
    assert out.column("a").nrows == 1000


def test_spill_cascades_to_disk(tmp_path):
    b = make_batch()
    size = b.device_size_bytes()
    cat = SpillableBatchCatalog(device_budget=size + 100,
                                host_budget=size + 100,
                                spill_dir=str(tmp_path))
    handles = [cat.register(make_batch(seed=i)) for i in range(3)]
    tiers = sorted(h.tier for h in handles)
    assert tiers == sorted([DISK, HOST, DEVICE])
    # disk roundtrip preserves data
    disk_h = next(h for h in handles if h.tier == DISK)
    out = disk_h.materialize()
    assert out.nrows == 1000
    assert out.column("s").to_pylist()[5] == "row-5"


def test_priority_order(tmp_path):
    b = make_batch()
    size = b.device_size_bytes()
    cat = SpillableBatchCatalog(device_budget=2 * size + 100,
                                spill_dir=str(tmp_path))
    cold = cat.register(make_batch(seed=1), priority=-1000)
    hot = cat.register(make_batch(seed=2), priority=1000)
    cat.register(make_batch(seed=3), priority=0)
    assert cold.tier == HOST
    assert hot.tier == DEVICE


def test_coalesce_iterator(tmp_path):
    cat = SpillableBatchCatalog(spill_dir=str(tmp_path))
    batches = [make_batch(100, seed=i) for i in range(5)]
    out = list(coalesce_iterator(iter(batches), RequireSingleBatch(),
                                 catalog=cat))
    assert len(out) == 1 and out[0].nrows == 500
    small = TargetSize(batches[0].device_size_bytes() * 2 + 1)
    out2 = list(coalesce_iterator(iter(batches), small, catalog=cat))
    assert len(out2) >= 2
    assert sum(b.nrows for b in out2) == 500


def test_host_bitflip_caught_on_restore(tmp_path):
    from spark_rapids_tpu.robustness import inject as I
    from spark_rapids_tpu.robustness.faults import CorruptionFault
    b = make_batch()
    cat = SpillableBatchCatalog(device_budget=1 << 30,
                                spill_dir=str(tmp_path))
    h = cat.register(b)
    h.spill_to_host()
    cat.device_bytes -= h.size_bytes
    cat.host_bytes += h.size_bytes
    with I.injected("spill.corrupt.host", kind="corrupt",
                    all_threads=True) as rule:
        with pytest.raises(CorruptionFault):
            h.materialize()
    assert rule.fired == 1
    # never returns wrong bytes: the batch is dropped, not served
    assert h.closed
    assert cat.stats()["num_handles"] == 0


def test_disk_bitflip_caught_on_restore(tmp_path):
    import os
    from spark_rapids_tpu.robustness import inject as I
    from spark_rapids_tpu.robustness.faults import CorruptionFault
    b = make_batch()
    size = b.device_size_bytes()
    cat = SpillableBatchCatalog(device_budget=size + 100,
                                host_budget=size + 100,
                                spill_dir=str(tmp_path))
    handles = [cat.register(make_batch(seed=i)) for i in range(3)]
    disk_h = next(h for h in handles if h.tier == DISK)
    path = disk_h._disk_path
    assert path and os.path.exists(path)
    # seeded: about one flip in a hundred of the compressed frame still
    # decodes to the same bytes (the CRC passes), which is no corruption
    # to catch; unseeded, this test failed that often
    with I.injected("spill.corrupt.disk", kind="corrupt",
                    all_threads=True, seed=0) as rule:
        with pytest.raises(CorruptionFault):
            disk_h.materialize()
    assert rule.fired == 1
    assert disk_h.closed
    # the dropped batch's spill file is unlinked with it
    assert not os.path.exists(path)


def test_clean_restores_verify_checksums(tmp_path):
    # integrity on (the default): host and disk round trips still
    # bit-exact, checksums stamped and verified silently
    b = make_batch()
    size = b.device_size_bytes()
    cat = SpillableBatchCatalog(device_budget=size + 100,
                                host_budget=size + 100,
                                spill_dir=str(tmp_path))
    assert cat.integrity_check
    handles = [cat.register(make_batch(seed=i)) for i in range(3)]
    for h in handles:
        assert h.tier == DEVICE or h._integrity_crc is not None
    disk_h = next(h for h in handles if h.tier == DISK)
    out = disk_h.materialize()
    assert out.column("s").to_pylist()[5] == "row-5"


def test_disk_write_is_atomic(tmp_path, monkeypatch):
    import os
    from spark_rapids_tpu.robustness.faults import SpillIOError
    b = make_batch()
    cat = SpillableBatchCatalog(device_budget=1 << 30,
                                spill_dir=str(tmp_path))
    h = cat.register(b)
    h.spill_to_host()
    # a crash between write and rename must leave nothing restorable
    monkeypatch.setattr(os, "replace",
                        lambda *a: (_ for _ in ()).throw(
                            OSError("simulated crash at rename")))
    with pytest.raises(SpillIOError):
        h.spill_to_disk()
    # still intact at HOST (nothing was lost), no partial spill file
    assert h.tier == HOST
    assert not [f for f in os.listdir(tmp_path)]
    monkeypatch.undo()
    h.spill_to_disk()
    assert h.tier == DISK
    names = os.listdir(tmp_path)
    assert names and all(n.endswith(".tcf") for n in names)


def test_close_sweeps_orphaned_spill_files(tmp_path):
    import os
    b = make_batch()
    size = b.device_size_bytes()
    cat = SpillableBatchCatalog(device_budget=size + 100,
                                host_budget=size + 100,
                                spill_dir=str(tmp_path))
    handles = [cat.register(make_batch(seed=i)) for i in range(3)]
    disk_h = next(h for h in handles if h.tier == DISK)
    # orphan a frame this catalog issued: the handle vanishes (crashed
    # restore) but its file and a torn .tmp sibling stay behind
    orphan = disk_h._disk_path
    torn = orphan + ".tmp"
    with open(torn, "wb") as f:
        f.write(b"torn")
    cat._handles.pop(disk_h.id)
    # a FOREIGN catalog's frame in the same (shared) dir must survive
    foreign = os.path.join(tmp_path, "buf-999983.tcf")
    with open(foreign, "wb") as f:
        f.write(b"other catalog's live frame")
    cat.close()
    assert cat.stats()["num_handles"] == 0
    assert not os.path.exists(orphan)  # swept: ours
    assert not os.path.exists(torn)    # swept: ours
    assert os.path.exists(foreign)     # spared: not ours
    os.unlink(foreign)
    # catalog stays usable after close (spill dir re-created on demand)
    h = cat.register(make_batch(seed=9))
    h.spill_to_host()
    h.spill_to_disk()
    assert h.tier == DISK


def test_wedged_disk_writer_is_recoverable(tmp_path):
    # an UNBOUNDED hang in a disk-writer pool thread must not deadlock
    # the driving thread under the catalog lock: the cooperative pool
    # wait trips the spill.disk deadline and raises a TimeoutFault
    import time
    from spark_rapids_tpu.api.session import TpuSession
    from spark_rapids_tpu.robustness import inject as I
    from spark_rapids_tpu.robustness.faults import TimeoutFault
    TpuSession({"spark.rapids.tpu.watchdog.deadline.spill.disk": 200})
    cat = SpillableBatchCatalog(device_budget=1 << 30,
                                host_budget=1 << 30,
                                spill_dir=str(tmp_path),
                                disk_write_threads=2)
    hs = [cat.register(make_batch(seed=i)) for i in range(2)]
    for h in hs:
        freed = h.spill_to_host()
        cat.device_bytes -= freed
        cat.host_bytes += freed
    cat.host_budget = 0  # force both to disk in ONE pass (pool path)
    rule = I.inject("spill.disk", kind="delay", delay_s=None,
                    count=2, all_threads=True)
    t0 = time.monotonic()
    try:
        with pytest.raises(TimeoutFault):
            cat.ensure_budget()
        assert time.monotonic() - t0 < 5
    finally:
        I.remove(rule)  # un-wedge the abandoned writers


def test_handle_close_survives_unlink_failure(tmp_path, monkeypatch):
    import os
    b = make_batch()
    size = b.device_size_bytes()
    cat = SpillableBatchCatalog(device_budget=size + 100,
                                host_budget=size + 100,
                                spill_dir=str(tmp_path))
    handles = [cat.register(make_batch(seed=i)) for i in range(3)]
    disk_h = next(h for h in handles if h.tier == DISK)
    monkeypatch.setattr(os, "unlink",
                        lambda *a: (_ for _ in ()).throw(
                            OSError("unlink denied")))
    disk_h.close()  # must not raise, must deregister
    monkeypatch.undo()
    assert disk_h.closed
    assert disk_h.id not in cat._handles


def test_semaphore():
    sem = TpuSemaphore(permits=1)
    with sem:
        with sem:  # re-entrant for same thread
            pass
    import threading
    acquired = []

    def worker():
        with sem:
            acquired.append(1)

    with sem:
        t = threading.Thread(target=worker)
        t.start()
        t.join(timeout=0.2)
        assert not acquired  # blocked while held
    t.join(timeout=2)
    assert acquired
