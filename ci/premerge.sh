#!/usr/bin/env bash
# Local premerge runner — the same gate as .github/workflows/ci.yml for
# environments without GitHub runners (reference analog:
# jenkins/spark-premerge-build.sh:31-52).  Fails on: any test failure,
# generated-doc drift, or public-API manifest drift.
set -euo pipefail
cd "$(dirname "$0")/.."

export JAX_PLATFORMS=cpu
export JAX_ENABLE_X64=1
export XLA_FLAGS="--xla_force_host_platform_device_count=8 --xla_cpu_enable_fast_math=false ${XLA_FLAGS:-}"

echo "== unit tests (virtual 8-device CPU mesh) =="
python -m pytest tests/ -q --maxfail=20 -m 'not chaos'

echo "== chaos suite (fault injection + recovery ladder + hang/corruption + concurrent spray w/ isolation gate) =="
bash ci/chaos.sh

echo "== perf smoke (deterministic budgets: host-sync counts + shuffle collective-count — packed q3-shape exchange <= 3 all_to_all vs >= 8 unpacked; no timing) =="
python -m pytest tests/ -q -m perf --maxfail=5

echo "== trace-validation smoke (distributed TPC-H q3 with tracing on: export parses, rollup sums within wall, unattributed < 20%, span-derived overlap matches exchangeOverlapMs) =="
python - <<'PY'
import glob
import os
import tempfile

from spark_rapids_tpu.api.session import TpuSession
from spark_rapids_tpu.models import tpch
from spark_rapids_tpu.parallel.mesh import make_mesh
from spark_rapids_tpu.tools.traceview import (load_trace, summarize,
                                              validate_chrome_trace)

td = tempfile.mkdtemp(prefix="tpu-trace-smoke-")
s = TpuSession({"spark.rapids.tpu.trace.dir": td,
                "spark.rapids.tpu.exchange.async.enabled": True},
               mesh=make_mesh(8))
q3 = tpch.q3(tpch.load(s, tpch.gen_tables(sf=0.01)))
rows = q3.to_pandas()
assert len(rows), "q3 returned nothing"
sp = s.last_span_stats
assert sp and sp["events"], sp
# the exclusive-time rollup must sum WITHIN the wall budget (spans on
# the single distributed driving thread cannot attribute more time
# than the envelope measured) and cover >= 80% of it
assert sp["exclusiveMs"] <= sp["wallMs"] * 1.05, sp
assert sp["unattributedFrac"] < 0.20, sp
# the PR9 overlap number, reproduced from spans alone (within 10%)
sh = s.last_shuffle_stats or {}
ov = sh.get("exchangeOverlapMs", 0.0)
assert ov > 0, sh
assert abs(sp["overlapMs"] - ov) <= 0.10 * ov + 0.5, (sp["overlapMs"], ov)
files = glob.glob(os.path.join(td, "*.json"))
assert files, "no trace exported"
for f in files:
    problems = validate_chrome_trace(load_trace(f))
    assert not problems, (f, problems)
s.stop()
print(summarize(load_trace(files[-1]), top=6))
print(f"trace smoke OK (unattributed={sp['unattributedFrac']:.1%}, "
      f"span overlap={sp['overlapMs']:.1f}ms vs metric {ov:.1f}ms, "
      f"{len(files)} file(s) valid)")
PY

echo "== docgen drift check =="
tmp=$(mktemp -d)
python -m spark_rapids_tpu.tools.docgen "$tmp"
diff -u docs/configs.md "$tmp/configs.md"
diff -u docs/supported_ops.md "$tmp/supported_ops.md"
rm -rf "$tmp"

echo "== API manifest audit =="
python -m spark_rapids_tpu.tools.api_validation

echo "== driver entry compile check =="
python - <<'PY'
import jax
jax.config.update("jax_platforms", "cpu")
import __graft_entry__ as g
fn, args = g.entry()
jax.jit(fn)(*args)
g.dryrun_multichip(8)
print("entry + dryrun_multichip OK")
PY

echo "PREMERGE OK"
