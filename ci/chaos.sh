#!/usr/bin/env bash
# Chaos gate: run the fault-injection suite standalone so the injection
# points and the recovery ladder cannot silently rot (tests/test_chaos.py
# arms every named point in robustness/inject.py and requires the query
# to answer with clean-run results).  CPU-only — the virtual 8-device
# mesh exercises the distributed demotion rungs without TPU hardware.
set -euo pipefail
cd "$(dirname "$0")/.."

export JAX_PLATFORMS=cpu
export JAX_ENABLE_X64=1
export XLA_FLAGS="--xla_force_host_platform_device_count=8 --xla_cpu_enable_fast_math=false ${XLA_FLAGS:-}"

echo "== chaos suite (fault injection + recovery ladder) =="
python -m pytest tests/ -q -m chaos --maxfail=5

echo "== hang/corruption spray (delay + corrupt rules, short deadlines) =="
# bounded wedges (0.2s) at EVERY registered injection point plus bit
# flips on both spill restore tiers, under tight CPU-scale watchdog
# deadlines; the query must still answer with clean-run results
python - <<'PY'
import numpy as np
import pandas as pd

from spark_rapids_tpu.api import functions as F
from spark_rapids_tpu.api.session import TpuSession
from spark_rapids_tpu.robustness import inject as I

s = TpuSession({
    "spark.rapids.tpu.watchdog.defaultDeadlineMs": 500,
    "spark.rapids.tpu.watchdog.queryDeadlineMs": 30_000,
    "spark.rapids.memory.tpu.deviceLimitBytes": 65536,
    "spark.rapids.sql.recovery.backoffMs": 5,
})
rng = np.random.default_rng(0)
pdf = pd.DataFrame({"k": rng.integers(0, 50, 4000),
                    "v": rng.normal(size=4000)})
df = (s.create_dataframe(pdf).group_by("k")
      .agg(F.sum(F.col("v")).alias("sv"),
           F.count(F.col("v")).alias("c")))
want = df.to_pandas().sort_values("k", ignore_index=True)
rules = []
try:
    for point in I.injection_points():
        rules.append(I.inject(point, kind="delay", delay_s=0.2,
                              count=2, probability=0.5, seed=7,
                              all_threads=True))
    for point in ("spill.corrupt.host", "spill.corrupt.disk"):
        rules.append(I.inject(point, kind="corrupt", count=2,
                              probability=0.5, seed=11,
                              all_threads=True))
    got = df.to_pandas().sort_values("k", ignore_index=True)
finally:
    for r in rules:
        I.remove(r)
pd.testing.assert_frame_equal(got, want)
print("hang/corruption spray OK "
      f"(recovery trail: {[r['action'] for r in s.recovery_log]})")
PY

echo "== checkpoint spray (delay + corrupt + oom across exchange/spill points, checkpointing on AND off) =="
# distributed two-stage plan on the virtual 8-device mesh; sprayed
# faults land mid-plan so stage checkpoints actually resume.  Both
# checkpoint settings must answer with clean-run results — partial
# recovery is an optimization, never a correctness knob.
python - <<'PY'
import numpy as np
import pandas as pd

from spark_rapids_tpu.api import functions as F
from spark_rapids_tpu.api.session import TpuSession
from spark_rapids_tpu.parallel.mesh import make_mesh
from spark_rapids_tpu.memory import retry as _retry  # registers memory.oom
from spark_rapids_tpu.robustness import inject as I
from spark_rapids_tpu.robustness.checkpoint import checkpoint_metrics

rng = np.random.default_rng(1)
pdf = pd.DataFrame({"k": rng.integers(0, 50, 4000),
                    "v": rng.normal(size=4000)})

SPRAY = (("shuffle.exchange", "raise"), ("shuffle.exchange", "delay"),
         ("checkpoint.write", "delay"), ("checkpoint.restore", "corrupt"),
         ("spill.corrupt.host", "corrupt"), ("memory.oom", "raise"))

for enabled in (True, False):
    s = TpuSession({
        "spark.rapids.sql.recovery.checkpoint.enabled": enabled,
        "spark.rapids.tpu.watchdog.defaultDeadlineMs": 500,
        "spark.rapids.sql.recovery.backoffMs": 5,
    }, mesh=make_mesh(8))
    df = (s.create_dataframe(pdf).group_by("k")
          .agg(F.sum(F.col("v")).alias("sv"),
               F.count(F.col("v")).alias("c")).orderBy("k"))
    want = df.to_pandas()
    checkpoint_metrics.reset()
    with I.scoped_rules():
        for point, kind in SPRAY:
            I.inject(point, kind=kind, count=2, probability=0.5,
                     seed=29, delay_s=0.2, all_threads=True)
        got = df.to_pandas()
    pd.testing.assert_frame_equal(
        got.sort_values("k", ignore_index=True),
        want.sort_values("k", ignore_index=True))
    m = checkpoint_metrics.snapshot()
    if not enabled:
        assert m["writes"] == 0, m
    print(f"checkpoint spray OK (enabled={enabled}, "
          f"writes={m['writes']} resumes={m['resumes']} "
          f"invalid={m['invalid']}, "
          f"trail: {[r['action'] for r in s.recovery_log]})")
PY

echo "== fused-wire spray (fusion.wire on; exchange/spill/oom faults) =="
# wire-fused distributed stages — the warm speculative launch
# folds the wire packer into the compute program (one launch
# per shard, pinned by fusedWireStages) and exchange faults
# then land on the fused program.  Gates: bit-exact answers,
# clean recovery trails.
python - <<'PY'
import numpy as np
import pandas as pd

from spark_rapids_tpu.api import functions as F
from spark_rapids_tpu.api.session import TpuSession
from spark_rapids_tpu.exec.fusion import fusion_metrics
from spark_rapids_tpu.memory import retry as _retry  # registers memory.oom
from spark_rapids_tpu.parallel.mesh import make_mesh
from spark_rapids_tpu.robustness import inject as I

rng = np.random.default_rng(7)
# sparse 2^40 keyspace: the coded dense-directory path refuses, so the
# sort / segment kernel feeds the fused wire
uni = np.unique(rng.integers(0, 1 << 40, 8000, dtype=np.int64))[:2000]
pdf = pd.DataFrame({"k": uni[rng.integers(0, len(uni), 4000)],
                    "v": rng.integers(0, 1000, 4000).astype(np.float64)})


def plan(s):
    return (s.create_dataframe(pdf).group_by("k")
            .agg(F.sum(F.col("v")).alias("sv"),
                 F.count(F.col("v")).alias("c")).orderBy("k"))


base = TpuSession({})
want = plan(base).to_pandas().sort_values("k", ignore_index=True)
base.stop()

# warm speculative launches fold the wire packer into the compute
# program (fusedWireStages pins it); sprayed faults then land on the
# fused exchange and every answer must still be bit-exact.
fusion_metrics.reset()
s = TpuSession({
    "spark.rapids.tpu.fusion.wire.enabled": True,
    # looser than the checkpoint spray's 500ms: the sparse-key sort
    # path is legitimately slower than the coded directory on CPU, and
    # a trip inside the demoted (last) rung has no rung left to catch it
    "spark.rapids.tpu.watchdog.defaultDeadlineMs": 2000,
    "spark.rapids.sql.recovery.backoffMs": 5,
}, mesh=make_mesh(8))
df = plan(s)
pd.testing.assert_frame_equal(
    df.to_pandas().sort_values("k", ignore_index=True), want)  # cold
pd.testing.assert_frame_equal(
    df.to_pandas().sort_values("k", ignore_index=True), want)  # warm
m = fusion_metrics.snapshot()
assert m["fusedWireStages"] >= 1, m
with I.scoped_rules():
    for point, kind in (("shuffle.exchange", "raise"),
                        ("shuffle.exchange", "delay"),
                        ("spill.corrupt.host", "corrupt"),
                        ("memory.oom", "raise")):
        I.inject(point, kind=kind, count=2, probability=0.5,
                 seed=31, delay_s=0.2, all_threads=True)
    got = df.to_pandas().sort_values("k", ignore_index=True)
pd.testing.assert_frame_equal(got, want)
m = fusion_metrics.snapshot()
print(f"fused-wire spray OK (fusedWireStages={m['fusedWireStages']}, "
      f"trail: {[r['action'] for r in s.recovery_log]})")
s.stop()
PY

echo "== continuous-ingest soak: join + window + top-N shapes (N ticks under chaos spray, exact-result + bounded-memory/state gates) =="
# THREE standing queries — join-enrich-then-aggregate with a top-N
# post chain, windowed aggregation with watermark eviction, and the
# original plain aggregate — each ingest one appended parquet file
# per tick while delay/raise/corrupt/oom rules spray every tick's
# executions (the incremental points plus the exchange and spill
# surfaces).  Gates: every tick's answer on every shape is EXACTLY
# the one-shot recompute over everything ingested so far (the
# windowed oracle filtered by the tick's own committed watermark;
# epoch rollback may degrade a tick to full recompute — never to
# wrong bytes), memory is bounded (spill-catalog device bytes and
# process RSS plateau instead of growing with tick count), and the
# windowed shape's STATE is bounded — watermark eviction holds state
# bytes at a plateau under infinite-style ingest with zero stale or
# resurrected windows.
python - <<'PY'
import os
import shutil
import tempfile

import numpy as np
import pandas as pd

from spark_rapids_tpu.api import functions as F
from spark_rapids_tpu.api.session import TpuSession
from spark_rapids_tpu.parallel.mesh import make_mesh
from spark_rapids_tpu.memory import retry as _retry  # registers memory.oom
from spark_rapids_tpu.robustness import inject as I
from spark_rapids_tpu.robustness import incremental as _inc  # registers points
from spark_rapids_tpu.robustness.incremental import incremental_metrics

TICKS = 8
SPRAY = (("io.read", dict(kind="raise", count=2, probability=0.4)),
         ("shuffle.exchange", dict(kind="raise", count=2,
                                   probability=0.4)),
         ("shuffle.exchange", dict(kind="delay", delay_s=0.2, count=1,
                                   probability=0.3)),
         ("memory.oom", dict(kind="raise", count=1, probability=0.3)),
         ("incremental.state.restore", dict(kind="corrupt", count=1,
                                            probability=0.3)),
         ("incremental.state.write", dict(kind="raise", count=1,
                                          probability=0.2)),
         ("checkpoint.restore", dict(kind="corrupt", count=1,
                                     probability=0.2)),
         ("spill.corrupt.host", dict(kind="corrupt", count=1,
                                     probability=0.3)))

def rss_mb():
    with open("/proc/self/status") as f:
        for line in f:
            if line.startswith("VmRSS:"):
                return int(line.split()[1]) / 1024.0
    return 0.0

d = tempfile.mkdtemp(prefix="tpu-ingest-soak-")
rng = np.random.default_rng(13)
def write(i):
    pdf = pd.DataFrame({"k": rng.integers(0, 50, 4000),
                        "v": rng.integers(0, 1000, 4000).astype(np.float64)})
    p = os.path.join(d, f"b{i:03d}.parquet")
    pdf.to_parquet(p, index=False)
    return p

def write_win(i, tick):
    pdf = pd.DataFrame({
        "k": rng.integers(0, 10, 3000),
        "v": rng.integers(0, 1000, 3000).astype(np.float64),
        "ts": pd.to_datetime("2024-01-01") + pd.to_timedelta(
            tick * 600 + rng.integers(0, 600, 3000), unit="s")})
    p = os.path.join(d, f"w{i:03d}.parquet")
    pdf.to_parquet(p, index=False)
    return p

s = TpuSession({"spark.rapids.sql.recovery.backoffMs": 5,
                "spark.rapids.tpu.watchdog.defaultDeadlineMs": 15000,
                # ISSUE 11: the state/spill frames this soak's corrupt
                # rules flip are COMPRESSED (the shared host codec) —
                # the incremental.state.restore spray therefore covers
                # the compressed-state leg of the codec-corruption gate
                "spark.rapids.tpu.encoding.storage.hostCodec": "lz4",
                "spark.rapids.tpu.incremental.tiers": "host,disk",
                # ISSUE 14: watermark eviction two buckets behind the
                # newest event time — the bounded-state gate's knob
                "spark.rapids.tpu.incremental.watermarkDelayMs": 1200000},
               mesh=make_mesh(8))
incremental_metrics.reset()

# shape 1: join-enrich-then-aggregate with a provable top-N chain
dim = pd.DataFrame({"k": np.arange(50),
                    "w": (np.arange(50) % 7 + 1).astype(np.float64)})
dim_agg = s.create_dataframe(dim).groupBy("k").agg(F.max("w").alias("w"))
fj = [write(0), write(1)]
df_j = (s.read.parquet(*fj).join(dim_agg, "k").groupBy("k")
        .agg(F.sum((F.col("v") * F.col("w")).alias("vw")).alias("s"),
             F.count("v").alias("c"))
        .orderBy(F.col("k").desc()).limit(20))
run_j = s.incremental(df_j)
assert run_j._spec is not None and run_j._spec.join_type == "inner"
assert run_j._spec.trim_n == 20

# shape 2: windowed aggregation with watermark eviction
fw = [write_win(0, 0), write_win(1, 1)]
df_w = (s.read.parquet(*fw)
        .groupBy(F.window("ts", "10 minutes"), "k")
        .agg(F.sum("v").alias("sv"), F.count("v").alias("c"))
        .orderBy("window.start", "k"))
run_w = s.incremental(df_w)
assert run_w._spec is not None and run_w._spec.window_end == "window.end"

# shape 3: the original plain mergeable aggregate
fa = [write(100), write(101)]
df_a = (s.read.parquet(*fa).groupBy("k")
        .agg(F.sum("v").alias("sv"), F.count("v").alias("c"),
             F.avg("v").alias("av")).orderBy("k"))
run_a = s.incremental(df_a)

for r in (run_j, run_w, run_a):
    r.tick()  # cold epochs, no chaos

raised = 0
dev, rss, wstate = [], [], []
try:
    for t in range(TICKS):
        pj, pw, pa = write(2 + t), write_win(2 + t, 2 + t), write(102 + t)
        # a tick may RAISE when chaos kills both the delta attempt AND
        # the degraded recompute (e.g. a state.write fault landing on
        # the recompute path) — the PR7 contract is that the committed
        # epoch is untouched and the files re-ingest on retry; the
        # post-spray retry below exercises exactly that
        results = {}
        with I.scoped_rules():
            for point, kw in SPRAY:
                I.inject(point, seed=100 + t, all_threads=True, **kw)
            for name, runner, paths in (("j", run_j, [pj]),
                                        ("w", run_w, [pw]),
                                        ("a", run_a, [pa])):
                try:
                    results[name] = runner.tick(paths)
                except Exception:
                    results[name] = None
        for name, runner, paths in (("j", run_j, [pj]),
                                    ("w", run_w, [pw]),
                                    ("a", run_a, [pa])):
            if results[name] is None:  # spray disarmed: clean retry
                raised += 1
                results[name] = runner.tick(paths)
        got_j = results["j"].to_pandas()
        got_w = results["w"].to_pandas()
        got_a = results["a"].to_pandas()
        # one-shot recompute oracles over everything ingested (each
        # runner keeps its standing df's scan in step), chaos disarmed;
        # the windowed oracle applies the tick's OWN committed
        # watermark — stale or resurrected windows would diverge
        pd.testing.assert_frame_equal(got_j, df_j.to_pandas())
        wm = run_w.last_tick_info["watermark"]
        # canonical eviction semantics (the test helper's oracle):
        # null-window buckets never expire, so the filter keeps them
        pd.testing.assert_frame_equal(
            got_w, df_w.filter(
                F.col("window.end").isNull() |
                (F.col("window.end") > pd.Timestamp(wm, unit="us")))
            .to_pandas())
        pd.testing.assert_frame_equal(got_a, df_a.to_pandas())
        dev.append(s.memory_catalog.stats()["device_bytes"])
        rss.append(rss_mb())
        wstate.append(run_w.store.state_bytes)
finally:
    for r in (run_j, run_w, run_a):
        r.close()
    s.stop()
    shutil.rmtree(d, ignore_errors=True)

m = incremental_metrics.snapshot()
# bounded memory: state size is per-group (and per-LIVE-window), not
# per-ingested-row — device watermark and RSS plateau, not grow
assert dev[-1] <= max(dev[:2]) + (16 << 20), dev
assert rss[-1] - rss[1] < 400.0, rss
# bounded state: watermark eviction holds the windowed shape's state
# bytes at a plateau across 8 infinite-style ingest ticks
assert wstate[-1] <= max(wstate[:3]) + 4096, wstate
assert m["watermarkEvictedBuckets"] >= 4, m
assert m["commits"] >= 3 * TICKS, m
assert m["joinTicks"] + m["windowTicks"] + m["topnTicks"] >= 1, m
print(f"ingest soak OK ({TICKS} chaos ticks x 3 shapes exact, "
      f"raised+retried={raised}, "
      f"incremental={m['incrementalTicks']} full={m['fullRecomputes']} "
      f"rollbacks={m['rollbacks']} stateBytes={m['stateBytes']} "
      f"wmEvicted={m['watermarkEvictedBuckets']}bkt/"
      f"{m['watermarkEvictedBytes']}B, "
      f"device_bytes={dev[-1]} rssΔ={rss[-1]-rss[1]:.0f}MB "
      f"windowState={wstate})")
PY

echo "== jit-cache corruption/version spray (persistent tier degraded, exact results) =="
# populate a persistent jit-cache dir, then attack it every way the
# tier must survive: seeded bit flips at the jitcache.load fire_mutate
# hook, on-disk truncation, a header stamped by a different jax
# version, and raise/delay rules on the load path.  Every degraded
# load must fall back to a fresh compile — the query answers with
# clean-run results, wrong executables are never run.
python - <<'PY'
import glob
import json
import os
import shutil
import tempfile

import numpy as np
import pandas as pd

from spark_rapids_tpu.api import functions as F
from spark_rapids_tpu.api.session import TpuSession
from spark_rapids_tpu.ops import jit_cache
from spark_rapids_tpu.robustness import inject as I

d = tempfile.mkdtemp(prefix="tpu-jitcache-chaos-")
rng = np.random.default_rng(5)
pdf = pd.DataFrame({"k": rng.integers(0, 50, 4000),
                    "v": rng.normal(size=4000)})
try:
    s = TpuSession({"spark.rapids.tpu.jitCache.dir": d,
                    "spark.rapids.sql.recovery.backoffMs": 5})
    df = (s.create_dataframe(pdf)
          .filter(F.col("v") > -1.0)
          .select((F.col("v") * 2.0).alias("v2"), F.col("k"))
          .group_by("k").agg(F.sum(F.col("v2")).alias("sv"),
                             F.count(F.col("v2")).alias("c")))
    jit_cache.clear()
    want = df.to_pandas().sort_values("k", ignore_index=True)
    entries = glob.glob(os.path.join(d, "*.jit"))
    assert entries, "persistent tier wrote nothing"

    def fresh():  # simulate a fresh process against the same dir
        jit_cache.clear()
        jit_cache.configure_persistent(None)
        jit_cache.configure_persistent(d)

    # pass 1: seeded bit flips via the fire_mutate hook (CRC gate)
    fresh()
    with I.scoped_rules():
        I.inject("jitcache.load", kind="corrupt", count=3,
                 probability=0.7, seed=17, all_threads=True)
        got = df.to_pandas().sort_values("k", ignore_index=True)
    pd.testing.assert_frame_equal(got, want)
    inv1 = jit_cache.persistent_info()["invalid"]
    assert inv1 >= 1, "corrupt rule never hit a load"

    # pass 2: on-disk truncation + a foreign-version header
    entries = sorted(glob.glob(os.path.join(d, "*.jit")))
    assert len(entries) >= 2, entries
    with open(entries[0], "r+b") as f:
        f.truncate(max(os.path.getsize(entries[0]) // 2, 8))
    raw = open(entries[1], "rb").read()
    head, _, payload = raw.partition(b"\n")
    hdr = json.loads(head)
    hdr["env"]["jax"] = "0.0.0-elsewhere"
    with open(entries[1], "wb") as f:
        f.write(json.dumps(hdr).encode() + b"\n" + payload)
    fresh()
    got = df.to_pandas().sort_values("k", ignore_index=True)
    pd.testing.assert_frame_equal(got, want)
    assert jit_cache.persistent_info()["invalid"] >= 2, \
        jit_cache.persistent_info()

    # pass 3: raise + bounded-delay rules on the load path
    fresh()
    with I.scoped_rules():
        I.inject("jitcache.load", count=2, probability=0.5, seed=23,
                 all_threads=True)
        I.inject("jitcache.load", kind="delay", delay_s=0.2, count=2,
                 probability=0.5, seed=29, all_threads=True)
        got = df.to_pandas().sort_values("k", ignore_index=True)
    pd.testing.assert_frame_equal(got, want)
    s.stop()
    print(f"jit-cache spray OK (invalid={jit_cache.persistent_info()['invalid']}, "
          f"entries={len(glob.glob(os.path.join(d, '*.jit')))})")
finally:
    jit_cache.configure_persistent(None)
    shutil.rmtree(d, ignore_errors=True)
PY

echo "== concurrent spray (N clients, faults keyed per query, isolation gate) =="
# 8 client threads share one session through the admission layer; half
# carry injected faults scoped to THEIR query via keyed injection
# scopes.  The isolation gate: every clean client's result is
# bit-identical to solo execution, zero robustness events float
# unattributed, and no clean query's trail shows recovery/corruption.
python - <<'PY'
import threading

import numpy as np
import pandas as pd

from spark_rapids_tpu.api import functions as F
from spark_rapids_tpu.api.session import TpuSession
from spark_rapids_tpu.memory import retry as _retry  # registers memory.oom
from spark_rapids_tpu.robustness import inject as I
import tempfile

logdir = tempfile.mkdtemp(prefix="tpu-chaos-events-")
s = TpuSession({
    "spark.rapids.tpu.eventLog.dir": logdir,
    "spark.rapids.sql.recovery.backoffMs": 5,
    # generous: the deadline must catch only the injected wedges, not
    # honest cold-compile slowness under 8-way thread contention
    "spark.rapids.tpu.watchdog.defaultDeadlineMs": 15000,
    "spark.rapids.memory.tpu.deviceLimitBytes": 1 << 16,
})
rng = np.random.default_rng(3)
pdf = pd.DataFrame({"k": rng.integers(0, 50, 4000),
                    "v": rng.normal(size=4000)})
df = (s.create_dataframe(pdf).group_by("k")
      .agg(F.sum(F.col("v")).alias("sv"),
           F.count(F.col("v")).alias("c")))
want = df.to_pandas().sort_values("k", ignore_index=True)
FLAVORS = {1: ("memory.oom", dict(count=8, all_threads=True)),
           # wedge LONGER than the 15s deadline so the timeout path is
           # genuinely exercised under concurrency: the trip must
           # cancel THIS client's token only (adds ~2x15s to the pass)
           3: ("memory.oom", dict(count=2, kind="delay", delay_s=20.0,
                                  all_threads=True)),
           5: ("spill.corrupt.host", dict(count=2, kind="corrupt",
                                          all_threads=True)),
           7: ("io.read", dict(count=2, all_threads=True))}
results, failures = {}, {}

def client(i):
    try:
        if i in FLAVORS:
            point, kw = FLAVORS[i]
            with I.scoped_rules(key=f"client{i}"):
                I.inject(point, **kw)
                got = df.to_pandas()
        else:
            got = df.to_pandas()
        results[i] = got.sort_values("k", ignore_index=True)
    except Exception as e:
        failures[i] = e

ts = [threading.Thread(target=client, args=(i,)) for i in range(8)]
[t.start() for t in ts]
[t.join() for t in ts]
for i in range(8):
    if i in results:
        pd.testing.assert_frame_equal(results[i], want)
    else:
        assert i in FLAVORS, f"clean client {i} failed: {failures[i]}"
        from spark_rapids_tpu.robustness.faults import classify
        assert classify(failures[i]).kind != "unknown", failures[i]
s.stop()
from spark_rapids_tpu.tools.eventlog import load_logs
app = load_logs(logdir)[0]
assert app.recovery == [], f"unattributed recovery: {app.recovery}"
assert app.corruption == [], f"unattributed corruption: {app.corruption}"
INJECTED = {"device_oom", "io_read", "spill_corruption", "timeout"}
dirty = [q.query_id for q in app.queries
         if q.recovery or q.corruption or q.budget]
for q in app.queries:
    kinds = {r.get("fault") for r in q.recovery}
    assert kinds <= INJECTED, (q.query_id, q.recovery)
clean_ok = [q.query_id for q in app.queries
            if q.succeeded and not q.recovery and not q.corruption
            and not q.watchdog and not q.budget]
assert len(clean_ok) >= 8 - len(FLAVORS) + 1, clean_ok
print(f"concurrent spray OK ({len(results)}/8 answered, "
      f"dirty queries={dirty}, maxConcurrent={app.max_concurrent()})")
PY

echo "== async exchange spray (2 concurrent clients, faults keyed per query, overlap + staging paths) =="
# Two client threads share one MESH session with the PR-9 data-movement
# features live (async exchange window + ragged slots, then host-RAM
# staging).  One client carries raise/delay rules scoped to ITS query on
# the async-exchange injection points; the other runs clean.  The gate:
# zero wrong results (both clients bit-identical to solo execution),
# zero unattributed robustness events, and the clean client's trail
# shows no recovery — cross-query interference is a failure.
python - <<'PY'
import tempfile
import threading

import numpy as np
import pandas as pd

from spark_rapids_tpu.api import functions as F
from spark_rapids_tpu.api.session import TpuSession
from spark_rapids_tpu.parallel.mesh import make_mesh
from spark_rapids_tpu.robustness import inject as I

rng = np.random.default_rng(9)
n = 4000
keys = np.where(rng.random(n) < 0.8, 1,
                rng.integers(0, 200, n)).astype(np.int64)
pdf = pd.DataFrame({"k": keys, "v": rng.normal(size=n)})
dim = pd.DataFrame({"k": np.arange(200, dtype=np.int64),
                    "w": rng.normal(size=200)})

def q(s):
    return (s.create_dataframe(pdf)
            .join(s.create_dataframe(dim), on="k")
            .group_by("k").agg(F.sum(F.col("v")).alias("sv"),
                               F.sum(F.col("w")).alias("sw"))
            .to_pandas().sort_values("k", ignore_index=True))

PASSES = [
    ("async+ragged", {
        "spark.rapids.tpu.exchange.async.enabled": True,
        "spark.rapids.tpu.shuffle.slot.ragged.enabled": True,
    }, [("exchange.async.resolve", dict(count=2, probability=0.7)),
        ("exchange.async.resolve", dict(count=1, kind="delay",
                                        delay_s=0.3)),
        ("dist.host_sync", dict(count=1, probability=0.5))]),
    ("host-staging", {
        "spark.rapids.tpu.exchange.hostStaging.thresholdBytes": 1,
    }, [("exchange.host_staging", dict(count=2, probability=0.7)),
        ("exchange.host_staging", dict(count=1, kind="delay",
                                       delay_s=0.3))]),
]
for name, extra, spray in PASSES:
    logdir = tempfile.mkdtemp(prefix="tpu-async-chaos-")
    s = TpuSession({
        "spark.rapids.tpu.eventLog.dir": logdir,
        "spark.rapids.sql.recovery.backoffMs": 5,
        "spark.rapids.sql.join.broadcastThresholdRows": 1,
        "spark.rapids.tpu.watchdog.defaultDeadlineMs": 15000,
        **extra}, mesh=make_mesh(8))
    want = q(s)  # solo warm-up is also the oracle
    results, failures = {}, {}

    def client(i):
        try:
            if i == 0:
                with I.scoped_rules(key="faulted"):
                    for point, kw in spray:
                        I.inject(point, seed=41 + i, **kw)
                    results[i] = q(s)
            else:
                results[i] = q(s)
        except Exception as e:  # noqa: BLE001 — gate below
            failures[i] = e

    ts = [threading.Thread(target=client, args=(i,)) for i in range(2)]
    [t.start() for t in ts]
    [t.join() for t in ts]
    assert not failures, f"{name}: {failures}"
    for i in range(2):
        pd.testing.assert_frame_equal(results[i], want)
    s.stop()
    from spark_rapids_tpu.tools.eventlog import load_logs
    app = load_logs(logdir)[0]
    assert app.recovery == [], f"unattributed recovery: {app.recovery}"
    dirty = [qq.query_id for qq in app.queries if qq.recovery]
    for qq in app.queries:
        kinds = {r.get("fault") for r in qq.recovery}
        assert kinds <= {"shuffle", "host_sync", "timeout"}, \
            (qq.query_id, qq.recovery)
    clean_ok = [qq.query_id for qq in app.queries
                if qq.succeeded and not qq.recovery
                and not qq.corruption]
    # warm-up + at least the clean client answered without recovery
    assert len(clean_ok) >= 2, (name, clean_ok, dirty)
    ov = s.exchange_overlap_metrics.snapshot()
    print(f"async exchange spray [{name}] OK (2 clients exact, "
          f"dirty={dirty}, async={int(ov['asyncExchanges'])} "
          f"staged={int(ov['hostStagedExchanges'])})")
PY

echo "== codec-corruption spray (compressed storage frames + wire dictionary, encoded knobs ON) =="
# ISSUE 11 gate: with every encoding knob on — compressed HOST spill
# frames (storage.hostCodec), encoded execution, and the compressed
# wire — bit flips in compressed spill/checkpoint/state frames and the
# wire dictionary-delta broadcast must degrade to recompute/decoded
# paths with typed events and EXACT results; never wrong bytes.
python - <<'PY'
import numpy as np
import pandas as pd

from spark_rapids_tpu.api import functions as F
from spark_rapids_tpu.api.session import TpuSession
from spark_rapids_tpu.memory.spill import integrity_metrics
from spark_rapids_tpu.robustness import inject as I

# -- compressed spill frames --------------------------------------------
integrity_metrics.reset()
s = TpuSession({
    "spark.rapids.tpu.encoding.storage.hostCodec": "lz4",
    "spark.rapids.tpu.encoding.execution.enabled": True,
    "spark.rapids.memory.tpu.deviceLimitBytes": 65536,
    "spark.rapids.sql.recovery.backoffMs": 5,
})
rng = np.random.default_rng(3)
pdf = pd.DataFrame({"k": np.array(["g%02d" % v for v in
                                   rng.integers(0, 40, 6000)]),
                    "v": rng.normal(size=6000)})
df = (s.create_dataframe(pdf).group_by("k")
      .agg(F.sum(F.col("v")).alias("sv"), F.count(F.col("v")).alias("c")))
want = df.to_pandas().sort_values("k", ignore_index=True)
rules = []
try:
    # single-process spill tiers only here — compressed checkpoint and
    # incremental-state frames are sprayed by the continuous-ingest
    # soak above, whose session now runs the host codec
    for point in ("spill.corrupt.host", "spill.corrupt.disk"):
        rules.append(I.inject(point, kind="corrupt", count=3,
                              probability=0.7, seed=13,
                              all_threads=True))
    got = df.to_pandas().sort_values("k", ignore_index=True)
finally:
    for r in rules:
        I.remove(r)
pd.testing.assert_frame_equal(got, want)
corr = sum(integrity_metrics.snapshot().values())
assert corr >= 1, "no compressed-frame corruption was ever detected"
print("codec storage spray OK (compressed-frame corruptions "
      f"detected={corr}, recovery trail: "
      f"{[r['action'] for r in s.recovery_log]})")
s.stop()

# -- wire dictionary-delta broadcast ------------------------------------
from spark_rapids_tpu.parallel.mesh import make_mesh
from spark_rapids_tpu.parallel.shuffle import metrics_for_session

s = TpuSession({
    "spark.rapids.tpu.encoding.wire.enabled": True,
    "spark.rapids.sql.recovery.backoffMs": 5,
}, mesh=make_mesh(8))
df2 = (s.create_dataframe(pdf).group_by("k")
       .agg(F.sum(F.col("v")).alias("sv")))
# corrupt the FIRST launch's delta (it carries the full dictionary; a
# later launch's delta would be empty — nothing left to broadcast)
with I.scoped_rules():
    I.inject("shuffle.wire.dict", kind="corrupt", count=2,
             probability=1.0, seed=17, all_threads=True)
    got2 = df2.to_pandas().sort_values("k", ignore_index=True)
wm = metrics_for_session(s).snapshot()
assert wm["wireDictFallbacks"] >= 1, wm
want2 = df2.to_pandas().sort_values("k", ignore_index=True)
pd.testing.assert_frame_equal(got2, want2)
wm2 = metrics_for_session(s).snapshot()
assert wm2["encodedBytesSaved"] > wm["encodedBytesSaved"], \
    "post-corruption launch did not return to the encoded wire"
print("codec wire-dict spray OK (fallbacks="
      f"{wm['wireDictFallbacks']}, encoded wire re-armed)")
s.stop()
PY

echo "== tracing-on spray (raise/delay/corrupt with trace.dir set: results bit-identical, traces well-formed even for faulted queries, truncation marker honored at maxEvents=64) =="
# ISSUE 12 gate: the span runtime must be a pure observer.  The same
# spray as the hang/corruption pass runs with tracing ARMED and a tiny
# maxEvents bound; the answer must equal the tracing-off clean run,
# every exported trace (including the faulted attempts') must validate
# against the Chrome trace-event schema, and the bounded buffers must
# announce truncation explicitly.
python - <<'PY'
import glob
import os
import tempfile

import numpy as np
import pandas as pd

from spark_rapids_tpu.api import functions as F
from spark_rapids_tpu.api.session import TpuSession
from spark_rapids_tpu.robustness import inject as I
from spark_rapids_tpu.tools.traceview import load_trace, validate_chrome_trace
from spark_rapids_tpu.utils import tracing

rng = np.random.default_rng(0)
pdf = pd.DataFrame({"k": rng.integers(0, 50, 4000),
                    "v": rng.normal(size=4000)})
# many batches (4 files x 256-row reader batches) so one attempt
# yields well over 64 spans — maxEvents=64 must really truncate
ddir = tempfile.mkdtemp(prefix="tpu-trace-chaos-data-")
paths = []
for i in range(4):
    p = os.path.join(ddir, f"part-{i}.parquet")
    pdf.iloc[i * 1000:(i + 1) * 1000].to_parquet(p, index=False)
    paths.append(p)

def build(s):
    return (s.read.parquet(*paths)
            .filter(F.col("v") > -3.0)
            .group_by("k")
            .agg(F.sum(F.col("v")).alias("sv"),
                 F.count(F.col("v")).alias("c")))

# oracle: tracing OFF, no chaos
s0 = TpuSession({"spark.rapids.sql.reader.batchSizeRows": 256})
want = build(s0).to_pandas().sort_values("k", ignore_index=True)
s0.stop()

td = tempfile.mkdtemp(prefix="tpu-trace-chaos-")
s = TpuSession({
    "spark.rapids.tpu.trace.dir": td,
    "spark.rapids.tpu.trace.maxEvents": 64,
    "spark.rapids.sql.reader.batchSizeRows": 256,
    "spark.rapids.tpu.watchdog.defaultDeadlineMs": 500,
    "spark.rapids.memory.tpu.deviceLimitBytes": 65536,
    "spark.rapids.sql.recovery.backoffMs": 5,
})
df = build(s)
with I.scoped_rules():
    for point in I.injection_points():
        I.inject(point, kind="delay", delay_s=0.2, count=2,
                 probability=0.5, seed=7, all_threads=True)
    for point in ("spill.corrupt.host", "spill.corrupt.disk"):
        I.inject(point, kind="corrupt", count=2, probability=0.5,
                 seed=11, all_threads=True)
    got = df.to_pandas().sort_values("k", ignore_index=True)
pd.testing.assert_frame_equal(got, want)  # bit-identical under tracing
sp = s.last_span_stats
assert sp and sp["events"], sp
s.stop()
tracing.configure(enabled=False)
files = glob.glob(os.path.join(td, "*.json"))
assert files, "no trace files under chaos"
truncated = 0
for f in files:
    obj = load_trace(f)
    problems = validate_chrome_trace(obj)
    assert not problems, (f, problems)
    if obj.get("truncated"):
        truncated += 1
        assert any(e.get("name") == "trace-truncated"
                   for e in obj["traceEvents"]), f
assert truncated >= 1, \
    "maxEvents=64 under a recovery ladder never truncated"
print(f"tracing-on spray OK (exact results, {len(files)} trace(s) "
      f"well-formed, {truncated} truncated with marker, "
      f"recovery trail: {[r['action'] for r in s.recovery_log]})")
PY

echo "== shared-cache spray (8 clients, file mutation + corrupt/raise/delay on resultcache.load + shared-store restore: exact answers, zero stale reads) =="
# ISSUE 13 gate: with the fair interleaver + result cache + shared
# stage cache ON, 8 client threads hammer a shared store while (a)
# corrupt/raise/delay rules rot the resultcache.load and
# checkpoint.restore (shared-store restore) paths and (b) an input
# file is REWRITTEN between waves.  Every answer must exactly match
# the oracle for the file set it ran against — a degraded load is a
# recompute MISS, a moved fingerprint is an invalidation, NEVER stale
# bytes — and invalidations must actually fire (>= 1 per pass).
python - <<'PY'
import os
import tempfile
import threading

import numpy as np
import pandas as pd

from spark_rapids_tpu.api import functions as F
from spark_rapids_tpu.api.session import TpuSession
from spark_rapids_tpu.parallel.mesh import make_mesh
from spark_rapids_tpu.robustness import inject as I

ddir = tempfile.mkdtemp(prefix="tpu-shared-cache-data-")
path = os.path.join(ddir, "fact.parquet")

def write_fact(scale):
    rng = np.random.default_rng(23)
    pd.DataFrame({
        "k": rng.integers(0, 32, 4000).astype(np.int64),
        "v": rng.normal(size=4000) * scale,
    }).to_parquet(path)

def oracle():
    pdf = pd.read_parquet(path)
    pdf = pdf[pdf.v > -1.0]
    out = pdf.groupby("k", as_index=False).v.sum()
    out = out.rename(columns={"v": "sv"})
    return out.sort_values("k", ignore_index=True)

write_fact(1.0)
s = TpuSession({
    "spark.rapids.tpu.serving.interleave.enabled": True,
    "spark.rapids.tpu.serving.resultCache.enabled": True,
    "spark.rapids.tpu.serving.sharedStage.enabled": True,
    "spark.rapids.sql.recovery.backoffMs": 5,
}, mesh=make_mesh(8))

def query():
    return (s.read.parquet(path).filter(F.col("v") > -1.0)
            .group_by("k").agg(F.sum(F.col("v")).alias("sv")))

def wave(n=8, per_client=3):
    want = oracle()
    errors = []

    def client():
        try:
            for _ in range(per_client):
                got = query().to_pandas().sort_values(
                    "k", ignore_index=True)
                pd.testing.assert_frame_equal(got, want)
        except Exception as e:  # noqa: BLE001 - surfaced below
            errors.append(e)

    threads = [threading.Thread(target=client) for _ in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors, errors[:1]

with I.scoped_rules():
    # rot both reuse load paths while the clients hammer the store
    I.inject("resultcache.load", kind="corrupt", count=2,
             probability=0.5, seed=29, all_threads=True)
    I.inject("resultcache.load", count=2, probability=0.3, seed=31,
             all_threads=True)
    I.inject("resultcache.load", kind="delay", delay_s=0.05, count=2,
             probability=0.3, seed=37, all_threads=True)
    I.inject("checkpoint.restore", kind="corrupt", count=2,
             probability=0.5, seed=41, all_threads=True)
    wave()
    # file MUTATION between waves: every post-mutation answer must
    # match the fresh oracle (fingerprint drift -> invalidation ->
    # recompute; a stale hit would fail the frame compare)
    write_fact(3.0)
    wave()
    write_fact(5.0)
    wave()

rc = s.result_cache.snapshot()
ss = s.shared_stages.snapshot()
assert rc["hits"] >= 1, rc
assert rc["invalidations"] >= 1, rc  # mutation + corrupt rules fired
assert ss["writes"] >= 1, ss
print("shared-cache spray OK (8 clients x 3 waves exact, "
      f"resultCache={rc}, sharedStages(writes={ss['writes']}, "
      f"splices={ss['resumes']}, invalid={ss['invalid']}))")
s.stop()
PY

echo "== cost-model spray (decisions on, corrupt store + raise/delay/corrupt over costmodel.load + exchange/read faults: answers bit-identical to knobs-off) =="
# ISSUE 15 gate: with spark.rapids.tpu.costModel.enabled the model
# decides every knob while (a) its evidence store starts CORRUPT, (b)
# raise/delay/corrupt rules rot every costmodel.load (evidence load +
# the QueryEnd ledger/persistence writes), and (c) exchange/read
# faults drive the recovery ladder mid-query — including through the
# model's own ReplanRequested path.  Every answer must be bit-
# identical to a knobs-off session's; a degraded load is built-in
# defaults with CostModelInvalid, never a failed or wrong query.
python - <<'PY'
import os
import tempfile

import numpy as np
import pandas as pd

import spark_rapids_tpu.plan.costmodel  # registers costmodel.load
from spark_rapids_tpu.api import functions as F
from spark_rapids_tpu.api.session import TpuSession
from spark_rapids_tpu.parallel.mesh import make_mesh
from spark_rapids_tpu.robustness import inject as I

ddir = tempfile.mkdtemp(prefix="tpu-costmodel-data-")
store = tempfile.mkdtemp(prefix="tpu-costmodel-store-")
n = 512
rng = np.random.default_rng(17)
fact = pd.DataFrame({"a": np.arange(n, dtype=np.int64),
                     "j": np.zeros(n, dtype=np.int64),
                     "x": rng.uniform(size=n)})
paths = []
for i in range(8):
    p = os.path.join(ddir, f"fact-{i}.parquet")
    fact.iloc[i * n // 8:(i + 1) * n // 8].to_parquet(p, index=False)
    paths.append(p)
dim = pd.DataFrame({"j": np.arange(16, dtype=np.int64),
                    "w": np.arange(16) * 1.5})

def queries(s):
    f = s.read.parquet(*paths)
    d = s.create_dataframe(dim)
    agg = f.groupBy("a").agg(F.max("j").alias("j"),
                             F.sum("x").alias("sx"))
    skew_join = agg.join(d, "j")          # skewed: replan territory
    grand = f.filter(F.col("x") > 0.1).agg(F.sum("x").alias("t"))
    return [("join", skew_join, ["a"]), ("agg", grand, ["t"])]

conf = {"spark.rapids.sql.join.broadcastThresholdRows": 4,
        "spark.rapids.sql.recovery.backoffMs": 5}
off = TpuSession(dict(conf), mesh=make_mesh(8))
want = {name: q.to_pandas().sort_values(keys, ignore_index=True)
        for name, q, keys in queries(off)}
off.stop()

# CORRUPT store from the start: a torn record plus valid lines
with open(os.path.join(store, "observations.jsonl"), "w") as fh:
    fh.write('{"site": "cm:aa", "rows": 64, "skew": 0.5}\n'
             '{"site": "cm:bb", "ro')

with I.scoped_rules():
    # corrupt applies at the construction-time fire_mutate (the ONLY
    # mutate site): the evidence bytes rot on top of the torn line;
    # the raise rule skips that load so it lands on the first
    # QueryEnd ledger/persistence write instead, and delays cover
    # later writes — every costmodel.load flavor really executes
    I.inject("costmodel.load", kind="corrupt", count=1,
             all_threads=True)
    I.inject("costmodel.load", count=1, skip=1, all_threads=True)
    I.inject("costmodel.load", kind="delay", delay_s=0.05, count=2,
             skip=2, all_threads=True)
    I.inject("shuffle.exchange", count=1, skip=2, all_threads=True)
    I.inject("io.read", count=1, skip=12, all_threads=True)
    s = TpuSession(dict(conf, **{
        "spark.rapids.tpu.costModel.enabled": True,
        "spark.rapids.tpu.costModel.dir": store,
    }), mesh=make_mesh(8))
    assert s.cost_model.invalid_loads >= 1, "corrupt load undetected"
    for round_ in range(2):  # round 2 runs on converged evidence
        for name, q, keys in queries(s):
            got = q.to_pandas().sort_values(keys, ignore_index=True)
            pd.testing.assert_frame_equal(
                got[want[name].columns], want[name],
                check_dtype=False)
    # both degrade legs fired: the corrupt/torn evidence LOAD and the
    # raise on a QueryEnd ledger write
    assert s.cost_model.invalid_loads >= 2, s.cost_model.invalid_loads
    print("cost-model spray OK (2 rounds exact, "
          f"invalid={s.cost_model.invalid_loads}, "
          f"replans={s.cost_model.replan_count}, "
          f"recovery={[r['fault'] for r in s.recovery_log]})")
    s.stop()
PY

echo "== fleet soak: 8 standing subscribers, shared-ingest rounds under kill/delay/corrupt spray (exactly-once sinks, bit-identical answers, fault isolation) =="
# ISSUE 16: an 8-subscriber fleet (4 join-enrich, 2 windowed with
# DIFFERENT watermark delays, 2 plain aggregates) ticks shared-ingest
# rounds while raise/delay/corrupt rules spray every surface a round
# crosses — the source read, exchanges, state write/restore,
# checkpoint restore, and the NEW incremental.sink.commit window
# between compute and epoch commit.  Gates: every committed tick's
# answer is bit-identical to its one-shot oracle (the windowed ones
# under their OWN committed watermark); every committed epoch emitted
# its SinkCommit exactly once (replays re-emit the same epoch,
# flagged; the eventlog health check proves zero duplicates); a
# faulted subscriber's co-subscribers commit clean answers in the
# same round and the faulted one catches up from its backlog on the
# next round.
python - <<'PY'
import os
import shutil
import tempfile

import numpy as np
import pandas as pd

from spark_rapids_tpu.api import functions as F
from spark_rapids_tpu.api.session import TpuSession
from spark_rapids_tpu.parallel.mesh import make_mesh
from spark_rapids_tpu.memory import retry as _retry  # registers memory.oom
from spark_rapids_tpu.robustness import inject as I
from spark_rapids_tpu.robustness import incremental as _inc  # registers points
from spark_rapids_tpu.robustness.incremental import incremental_metrics

ROUNDS = 6
SPRAY = (("io.read", dict(kind="raise", count=2, probability=0.3)),
         ("shuffle.exchange", dict(kind="raise", count=2,
                                   probability=0.3)),
         ("shuffle.exchange", dict(kind="delay", delay_s=0.2, count=1,
                                   probability=0.2)),
         ("incremental.state.write", dict(kind="raise", count=1,
                                          probability=0.25)),
         ("incremental.state.restore", dict(kind="corrupt", count=1,
                                            probability=0.25)),
         ("checkpoint.restore", dict(kind="corrupt", count=1,
                                     probability=0.2)),
         # the exactly-once window: kill between compute and commit,
         # and rot the staged payload so the CRC gate must catch it
         ("incremental.sink.commit", dict(kind="raise", count=1,
                                          probability=0.35)),
         ("incremental.sink.commit", dict(kind="corrupt", count=1,
                                          probability=0.35)))

d = tempfile.mkdtemp(prefix="tpu-fleet-soak-")
logdir = os.path.join(d, "events")
rng = np.random.default_rng(23)

# ONE append-only stream all 8 subscribers share: k/v for the join
# and plain-agg shapes, event-time ts for the windowed ones (each
# round's file lives in that round's 10-minute bucket)
def write(i, tick):
    n = 3000
    pdf = pd.DataFrame({
        "k": rng.integers(0, 40, n),
        "v": rng.integers(0, 1000, n).astype(np.float64),
        "ts": pd.to_datetime("2024-01-01") + pd.to_timedelta(
            tick * 600 + rng.integers(0, 600, n), unit="s")})
    p = os.path.join(d, f"b{i:03d}.parquet")
    pdf.to_parquet(p, index=False)
    return p

s = TpuSession({"spark.rapids.sql.recovery.backoffMs": 5,
                "spark.rapids.tpu.watchdog.defaultDeadlineMs": 15000,
                "spark.rapids.tpu.eventLog.dir": logdir,
                # cross-subscriber splices ride the epoch tier
                "spark.rapids.tpu.serving.sharedStage.enabled": True},
               mesh=make_mesh(8))
incremental_metrics.reset()

dim = pd.DataFrame({"k": np.arange(40),
                    "w": (np.arange(40) % 7 + 1).astype(np.float64)})
pdim = os.path.join(d, "dim.parquet")
dim.to_parquet(pdim, index=False)

fact0 = write(0, 0)
fleet = s.fleet()
dfs, wdfs = {}, {}
for i in range(4):  # join-enrich subscribers share the dim subtree
    dim_agg = (s.read.parquet(pdim).groupBy("k")
               .agg(F.max("w").alias("w")))
    dfs[f"j{i}"] = (s.read.parquet(fact0).join(dim_agg, "k")
                    .groupBy("k")
                    .agg(F.sum((F.col("v") * F.col("w")).alias("vw"))
                         .alias("sx"),
                         F.count("v").alias("c")).orderBy("k"))
    fleet.subscribe(dfs[f"j{i}"], name=f"j{i}", fact=fact0)
for i, delay in ((0, 1_200_000), (1, 3_600_000)):  # independent horizons
    wdfs[f"w{i}"] = (s.read.parquet(fact0)
                     .groupBy(F.window("ts", "10 minutes"), "k")
                     .agg(F.sum("v").alias("sv"),
                          F.count("v").alias("c"))
                     .orderBy("window.start", "k"))
    fleet.subscribe(wdfs[f"w{i}"], name=f"w{i}",
                    watermark_delay_ms=delay)
for i in range(2):
    dfs[f"a{i}"] = (s.read.parquet(fact0).groupBy("k")
                    .agg(F.sum("v").alias("sv"),
                         F.count("v").alias("c"),
                         F.avg("v").alias("av")).orderBy("k"))
    fleet.subscribe(dfs[f"a{i}"], name=f"a{i}")

fleet.tick()  # cold epochs, no chaos

# per-subscriber exactly-once ledger: committed epoch -> payload crc
ledger = {n: {} for n in fleet.subscribers}
raised = retried = 0
try:
    for t in range(ROUNDS):
        p = write(1 + t, 1 + t)  # the round's ONE appended file
        with I.scoped_rules():
            for point, kw in SPRAY:
                I.inject(point, seed=300 + t, all_threads=True, **kw)
            commits = fleet.tick([p])
        info = dict(fleet.last_round_info)

        def record(batch):
            for n, sc in batch.items():
                if sc is None:
                    continue
                led = ledger[n]
                if sc.replayed:  # sanctioned: SAME epoch, SAME crc
                    assert led.get(sc.epoch) == sc.crc, (n, sc)
                else:  # a NEW emission rides a NEVER-emitted epoch
                    assert sc.epoch not in led, (n, sc, sorted(led))
                    led[sc.epoch] = sc.crc

        record(commits)
        if info["failures"]:
            # isolation gate: a faulted subscriber is ALONE — every
            # co-subscriber still committed this round
            raised += info["failures"]
            for n, sc in commits.items():
                assert (sc is None) == \
                    (n in fleet.last_round_errors), (n, info)
            # catch-up round, chaos disarmed: backlogged files
            # re-offer and the faulted subscribers re-ingest
            commits = fleet.tick()
            retried += 1
            assert not fleet.last_round_errors, fleet.last_round_errors
            record(commits)
        for n, sc in commits.items():
            assert sc is not None, (n, info)
        # bit-identical gate: every subscriber's committed answer is
        # its one-shot recompute oracle, chaos disarmed (the runners
        # keep each standing df's scan in step)
        for n, df in dfs.items():
            pd.testing.assert_frame_equal(
                commits[n].df.to_pandas(), df.to_pandas())
        for n, df in wdfs.items():
            h = fleet._handles[n]
            wm = h.runner.last_tick_info["watermark"]
            pd.testing.assert_frame_equal(
                commits[n].df.to_pandas(),
                df.filter(
                    F.col("window.end").isNull() |
                    (F.col("window.end") > pd.Timestamp(wm, unit="us"))
                ).to_pandas())
    # the two windowed subscribers evicted on their OWN schedules
    tight = fleet._handles["w0"].runner.store
    loose = fleet._handles["w1"].runner.store
    assert tight.state_watermark > loose.state_watermark
    # every subscriber holds at most one sink record per committed
    # data round (replay rounds added none)
    for n in fleet.subscribers:
        st = fleet._handles[n].runner.store
        assert len(st._sink) <= 1 + ROUNDS, (n, sorted(st._sink))
finally:
    fleet.close()
    s.stop()
    m = incremental_metrics.snapshot()

# eventlog health: the duplicate-emission detector stayed quiet over
# the WHOLE soak trail (and the sink/fleet rollups flowed through)
from spark_rapids_tpu.tools.eventlog import load_logs
from spark_rapids_tpu.tools.profiling import (_incremental_problems,
                                              incremental_stats)
apps = load_logs(logdir)
stats = incremental_stats(apps)
assert stats["sink_commits"] >= 8 * (1 + ROUNDS) - ROUNDS, stats
assert stats["fleet_rounds"] >= 1 + ROUNDS, stats
for a in apps:
    evs = list(a.incremental) + [e for q in a.queries
                                 for e in q.incremental]
    dups = [p for p in _incremental_problems(a.session_id, evs)
            if "duplicate sink emission" in p]
    assert not dups, dups
shutil.rmtree(d, ignore_errors=True)
print(f"fleet soak OK ({ROUNDS} chaos rounds x 8 subscribers exact, "
      f"faulted+retried={raised}/{retried}, "
      f"sinkCommits={m['sinkCommits']} sinkReplays={m['sinkReplays']} "
      f"rollbacks={m['rollbacks']} "
      f"sourcePulls={stats['fleet_source_pulls']} "
      f"splices={stats['fleet_splices']})")
PY

echo "== template spray (prepared statements + template cache under corrupt/raise/delay on templatecache.load: exact answers, zero planning passes, rot invalidates then re-stores) =="
# ISSUE 17 gate: a prepared handle serves randomized literal bindings
# while corrupt/raise/delay rules rot every templatecache.load.  A
# degraded load is a recompute MISS on the handle's cached physical
# plan — never a wrong answer, never a failed query, and never a
# planning pass (prepare paid for planning once; cache rot must not
# smuggle one back in).  Corruption must actually land (CRC-gated
# invalidations >= 1) and the clean wave after the spray must hit
# again (rot evicts entries, it does not poison the tier).
python - <<'PY'
import numpy as np
import pandas as pd

from spark_rapids_tpu.api import functions as F
from spark_rapids_tpu.api.session import TpuSession
from spark_rapids_tpu.plan import overrides as OV
from spark_rapids_tpu.robustness import inject as I

rng = np.random.default_rng(7)
pdf = pd.DataFrame({"k": rng.integers(0, 16, 4000),
                    "v": rng.normal(size=4000),
                    "q": rng.uniform(1.0, 50.0, 4000)})
s = TpuSession({
    "spark.rapids.tpu.template.enabled": True,
    "spark.rapids.tpu.serving.resultCache.enabled": True,
    "spark.rapids.tpu.template.resultCache.enabled": True,
    "spark.rapids.sql.recovery.backoffMs": 5,
})
df = (s.create_dataframe(pdf)
      .filter((F.col("q") >= F.lit(5.0)) & (F.col("q") < F.lit(20.0)))
      .select((F.col("v") * F.col("q")).alias("rev"))
      .agg(F.sum(F.col("rev")).alias("revenue")))
h = s.prepare(df)
assert h.param_count == 2 and not h.refusals, h.describe()
VECTORS = [(5.0, 20.0), (7.5, 30.0), (2.0, 44.0), (11.0, 13.0)]
# warm wave: each binding computes once and stores a template entry
want = {vec: h.run(*vec) for vec in VECTORS}
p0 = OV.planning_passes()
with I.scoped_rules():
    I.inject("templatecache.load", kind="corrupt", count=3,
             probability=0.6, seed=43, all_threads=True)
    I.inject("templatecache.load", count=2, probability=0.4, seed=47,
             all_threads=True)
    I.inject("templatecache.load", kind="delay", delay_s=0.2, count=2,
             probability=0.4, seed=53, all_threads=True)
    for _ in range(2):
        for vec in VECTORS:
            assert h.run(*vec) == want[vec], vec
snap = s.result_cache.snapshot()
assert snap["templateHits"] >= 1, snap
assert snap["invalidations"] >= 1, "corrupt rule never rotted a load"
assert OV.planning_passes() == p0, \
    "cache rot smuggled a planning pass into a prepared repeat"
# clean wave: rot-invalidated entries were re-stored and hit again
for vec in VECTORS:
    assert h.run(*vec) == want[vec], vec
snap2 = s.result_cache.snapshot()
assert snap2["templateHits"] > snap["templateHits"], (snap, snap2)
s.stop()
print("template spray OK (4 bindings x 3 waves exact, "
      f"templateHits={snap2['templateHits']} "
      f"templateStores={snap2['templateStores']} "
      f"invalidations={snap2['invalidations']}, planning passes 0)")
PY

echo "== multi-host fleet spray (logical-host fleet, injected host loss + host_sync delays: shrink-rung recovery bit-identical, co-hosted queries clean, stale writer fenced) =="
# ISSUE 18 gate: a 2-host logical fleet (8-device mesh partitioned by
# fleet.logicalHosts, real HostMembership registry) loses a host
# mid-query — an injected HostLossFault on the fleet.heartbeat point,
# with bounded delays sprayed on dist.host_sync — and must recover
# through the ladder's SHRINK rung: mesh rebuilt over the survivors,
# answer bit-identical to the clean full-fleet run.  Co-hosted clean
# queries are counter-pinned at ZERO attributed recovery events, zero
# robustness events float unattributed, and a zombie writer still
# holding the pre-shrink fence token is REJECTED by the fleet cache
# (entry never written, FleetCacheFence health trail recorded).
python - <<'PY'
import tempfile

import numpy as np
import pandas as pd

from spark_rapids_tpu.api import functions as F
from spark_rapids_tpu.api.session import TpuSession
from spark_rapids_tpu.robustness import inject as I

logdir = tempfile.mkdtemp(prefix="tpu-fleet-chaos-events-")
s = TpuSession({
    "spark.rapids.sql.distributed.numShards": "8",
    "spark.rapids.tpu.fleet.logicalHosts": "2",
    "spark.rapids.tpu.fleet.membershipDir":
        tempfile.mkdtemp(prefix="tpu-fleet-chaos-members-"),
    "spark.rapids.tpu.fleet.cache.dir":
        tempfile.mkdtemp(prefix="tpu-fleet-chaos-cache-"),
    # un-rate-limit the heartbeat so the injected loss lands on the
    # query path's first membership check
    "spark.rapids.tpu.fleet.heartbeatMs": 1,
    "spark.rapids.tpu.eventLog.dir": logdir,
    "spark.rapids.sql.recovery.backoffMs": 5,
})
rng = np.random.default_rng(19)
pdf = pd.DataFrame({"k": rng.integers(0, 50, 4000),
                    "v": rng.normal(size=4000)})
df = (s.create_dataframe(pdf).group_by("k")
      .agg(F.sum(F.col("v")).alias("sv"),
           F.count(F.col("v")).alias("c")))
want = df.to_pandas().sort_values("k", ignore_index=True)
assert s.mesh.devices.size == 8
stale_tok = s.fleet_epoch  # the token a zombie would still hold
s.recovery_log.clear()
with I.scoped_rules():
    I.inject("fleet.heartbeat", count=1, all_threads=True)
    I.inject("dist.host_sync", kind="delay", delay_s=0.2, count=2,
             probability=0.5, seed=61, all_threads=True)
    got = df.to_pandas().sort_values("k", ignore_index=True)
pd.testing.assert_frame_equal(got, want)  # survivor bit-identical
actions = [r["action"] for r in s.recovery_log]
assert "shrink" in actions, actions
assert s.mesh.devices.size == 4, "mesh did not shrink to survivors"
# co-hosted clean queries: ZERO new attributed recovery events
n_events = len(s.recovery_log)
again = df.to_pandas().sort_values("k", ignore_index=True)
pd.testing.assert_frame_equal(again, want)
assert len(s.recovery_log) == n_events, s.recovery_log[n_events:]
# the zombie's publish: pre-shrink fence token, REJECTED + never read
assert not s.fleet_cache.publish("zombie-entry", {"x": 1}, stale_tok)
assert s.fleet_cache.counters["fenced"] == 1
assert s.fleet_cache.lookup("zombie-entry") is None
s.stop()
from spark_rapids_tpu.tools.eventlog import load_logs
app = load_logs(logdir)[0]
assert app.recovery == [], f"unattributed recovery: {app.recovery}"
for q in app.queries:
    kinds = {r.get("fault") for r in q.recovery}
    assert kinds <= {"host_loss"}, (q.query_id, q.recovery)
fleet_kinds = [e["kind"] for e in app.fleet]
for k in ("join", "shrink", "fence"):
    assert k in fleet_kinds, fleet_kinds
print("multi-host fleet spray OK (shrink recovery exact, "
      f"trail={actions}, fleet events={fleet_kinds}, "
      f"fenced={s.fleet_cache.counters['fenced']})")
PY

echo "== fail-slow spray (gray failure: one slow host, sub-deadline delays -> hedge + quarantine/rejoin, bit-identical) =="
# fail-SLOW, not fail-stop: host 1's staging/host_sync walls stretch via
# sub-hard-deadline delay rules and gossiped slow walls — no heartbeat
# loss ever trips.  Gates: every query bit-identical to the clean run,
# the mitigation rungs actually fire (hedge AND quarantine->rejoin),
# and co-hosted clean queries attribute ZERO recovery entries (a hedge
# is not a fault; the ladder stays silent throughout).
python - <<'PY'
import time

import numpy as np
import pandas as pd

from spark_rapids_tpu.api import functions as F
from spark_rapids_tpu.api.session import TpuSession
from spark_rapids_tpu.robustness import grayfailure as gf
from spark_rapids_tpu.robustness import inject as I

s = TpuSession({
    "spark.rapids.sql.distributed.numShards": "8",
    "spark.rapids.tpu.fleet.logicalHosts": "2",
    "spark.rapids.tpu.fleet.grayFailure.enabled": True,
    "spark.rapids.tpu.fleet.suspectWindow": 8,
    "spark.rapids.tpu.fleet.quarantineAfterMs": 30,
    "spark.rapids.tpu.fleet.rejoinAfterMs": 30,
    "spark.rapids.tpu.fleet.hedgeFloorMs": 25,
    "spark.rapids.tpu.exchange.hostStaging.thresholdBytes": 1,
    "spark.rapids.sql.join.broadcastThresholdRows": 1,
    # logical hosts auto-pick the DCN gather strategy, which never
    # host-stages; pin the ICI collective so the hedgeable tier runs
    "spark.rapids.tpu.shuffle.topology.strategy": "all_to_all",
    "spark.rapids.sql.recovery.backoffMs": 1,
})
rng = np.random.default_rng(23)
fact = pd.DataFrame({"k": rng.integers(0, 300, 4000),
                     "v": rng.normal(size=4000)})
dim = pd.DataFrame({"k": np.arange(300), "w": rng.normal(size=300)})

def q():
    return (s.create_dataframe(fact)
            .join(s.create_dataframe(dim), on="k")
            .group_by("k")
            .agg(F.sum(F.col("v")).alias("sv"),
                 F.sum(F.col("w")).alias("sw"))
            .to_pandas().sort_values("k", ignore_index=True))

want = q()  # clean oracle (already on the staging path)
assert s.exchange_overlap_metrics.snapshot()["hostStagedExchanges"] >= 2

t = s.gray_health
# host 1 turns fail-slow: its gossiped beat walls stretch 10x on every
# evidence point while host 0 stays at fleet speed — the exact payload
# a degraded peer's beat records would carry
rules = []
try:
    for _ in range(8):
        t.observe_wall(0, "exchange.host_staging", 10.0)
        t.observe_wall(0, "dist.host_sync", 5.0)
        t.observe_peer_walls(1, {"exchange.host_staging": 100.0,
                                 "dist.host_sync": 50.0})
    t.observe_beat(1, 1000.0)
    t.observe_beat(1, 1000.9)  # stretched beat interval, NOT silence
    t.poll()
    assert t.is_suspect(1), t.state
    # sub-hard-deadline wedges on the sick host's staging/sync writes
    # (far below any watchdog deadline: these are delays, not hangs)
    rules.append(I.inject("exchange.host_staging", kind="delay",
                          delay_s=0.4, count=1))
    rules.append(I.inject("dist.host_sync", kind="delay",
                          delay_s=0.05, count=2, probability=0.5,
                          seed=3, all_threads=True))
    got = q()  # hedged: healthy re-dispatch answers
    pd.testing.assert_frame_equal(got, want)
    c = t.query_counters()
    assert c["hedgesFired"] >= 1 and c["hedgesWon"] >= 1, c
    time.sleep(0.05)  # outlast quarantineAfterMs
    got = q()  # boundary drains the sick host (soft shrink)
    pd.testing.assert_frame_equal(got, want)
    assert int(s.mesh.devices.size) == 4, s.mesh.devices.size
    assert t.state[1] == gf.QUARANTINED
    assert 1 not in s.fleet_membership.lost  # slow, never judged lost
    # the host recovers: its gossiped walls come back to the fleet's
    # OWN observed medians on every evidence point (one still-slow
    # point would keep the score pinned) -> rejoin at the next boundary
    for _ in range(8):
        t.observe_peer_walls(1, t.local_walls())
    t.poll()
    time.sleep(0.05)
    got = q()
    pd.testing.assert_frame_equal(got, want)
    assert int(s.mesh.devices.size) == 8, s.mesh.devices.size
finally:
    for r in rules:
        I.remove(r)
# co-hosted clean queries: ZERO attributed recovery entries — the
# whole fail-slow story ran without ever engaging the fault ladder.
# Both TPC-H shapes: the join+group-by (q3-like) and a
# filter+aggregate (q6-like) on the restored full mesh.
assert s.recovery_log == [], s.recovery_log
again = q()
pd.testing.assert_frame_equal(again, want)
q6 = (s.create_dataframe(fact).filter(F.col("v") >= 0.0)
      .group_by("k").agg(F.sum(F.col("v")).alias("rev"))
      .to_pandas().sort_values("k", ignore_index=True))
q6_want = (fact[fact["v"] >= 0.0].groupby("k", as_index=False)
           .agg(rev=("v", "sum")).sort_values("k", ignore_index=True))
pd.testing.assert_frame_equal(q6, q6_want, check_dtype=False)
assert s.recovery_log == [], s.recovery_log
cc = t.query_counters()
print("fail-slow spray OK (hedges "
      f"{cc['hedgesFired']}/{cc['hedgesWon']}, quarantines "
      f"{cc['quarantines']}, rejoins {cc['rejoins']}, ladder silent)")
s.stop()
PY

echo "CHAOS OK"
