"""A cell's cold compile, rehearsed without a chip.

    python ci/cold_compile.py --workload tpcds_sf1.q7 --seed 7 [--log FILE] [--sf 0.01]

Runs ``benchmark/run.py --allow-cpu`` for the cell at its own scale on
the CPU with ``jax.jit`` wrapped: every program the run jits is also
lowered for a described ``v5e:2x2`` (the engine's ``default_backend()``
branches taken as on the chip) and compiled by the chip's compiler,
its name and shapes logged (and synced) before the compile starts, its
seconds after.  The driver's first run of a cell compiles everything,
and the chip's compiler can die in a long compile (``SIGSEGV``, exit
139: PERF.md, PR 27 and PR 30): the log's last ``compiling`` line then
names the program, and ``faulthandler`` the thread.  The ``compiled``
lines, sorted, are the cold start's cost by program.

Not captured: programs jax builds itself for eager ``jnp`` calls on
device arrays outside a jit (small elementwise ones in q7 and q3).
Nothing here is a device number.  One process at a time may load the
TPU's library: set ``ALLOW_MULTIPLE_LIBTPU_LOAD=1`` to run several.
"""

import argparse
import faulthandler
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--log", default=os.path.join(
        ROOT, "benchmark", ".cache", "cold_compile.jsonl"))
    ap.add_argument("--sf", default=None,
                    help="a smaller scale, to try the tool itself")
    args = ap.parse_args()
    faulthandler.enable()
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, ROOT)

    import jax
    import jax._src.api as api
    import jax._src.core as core
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one_chip = SingleDeviceSharding(topo.devices[0])
    os.makedirs(os.path.dirname(os.path.abspath(args.log)), exist_ok=True)
    out = open(args.log, "a")

    def log(**kv):
        out.write(json.dumps(kv, default=str) + "\n")
        out.flush()
        os.fsync(out.fileno())

    def spec(x):
        if isinstance(x, (jax.Array, np.ndarray)):
            return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)
        return x

    real_jit, real_backend = api.jit, jax.default_backend

    def compile_for_chip(jitted, name, a, k):
        from spark_rapids_tpu.ops import pallas_kernels as pk
        a, k = jax.tree.map(spec, (a, k))
        shapes = [(tuple(x.shape), str(x.dtype))
                  for x in jax.tree.leaves((a, k))
                  if isinstance(x, jax.ShapeDtypeStruct)]
        jax.default_backend = lambda: "tpu"
        pk.use_pallas.cache_clear()
        try:
            lowered = jitted.lower(*a, **k)
            log(ev="compiling", name=name, shapes=shapes[:12])
            t0 = time.perf_counter()
            lowered.compile()
            log(ev="compiled", name=name,
                seconds=round(time.perf_counter() - t0, 3))
        except Exception as e:  # noqa: BLE001 — what the chip would refuse
            log(ev="refused", name=name, error=repr(e)[:600])
        finally:
            jax.default_backend = real_backend
            pk.use_pallas.cache_clear()

    class Wrapped:
        def __init__(self, jitted, fun):
            self._jitted = jitted
            self._name = getattr(fun, "__name__", None) or repr(fun)

        def __call__(self, *a, **k):
            if any(isinstance(x, core.Tracer)
                   for x in jax.tree.leaves((a, k))):
                return self._jitted(*a, **k)
            before = self._jitted._cache_size()
            got = self._jitted(*a, **k)
            if self._jitted._cache_size() > before:   # a new program
                compile_for_chip(self._jitted, self._name, a, k)
            return got

        def __getattr__(self, name):
            return getattr(self._jitted, name)

    def jit(fun=None, *a, **k):
        if fun is None:
            return lambda f: jit(f, *a, **k)
        return Wrapped(real_jit(fun, *a, **k), fun)

    api.jit = jax.jit = jit
    # donation is part of the chip's programs; the CPU only warns
    from spark_rapids_tpu.ops import compiler
    compiler.donation_supported = lambda: True

    from benchmark import run
    rc = run.main(["--workload", args.workload, "--seed", str(args.seed),
                   "--seconds", "3", "--trace", "0", "--allow-cpu"]
                  + (["--sf", args.sf] if args.sf else []))
    log(ev="done", rc=rc)
    return rc


if __name__ == "__main__":
    sys.exit(main())
