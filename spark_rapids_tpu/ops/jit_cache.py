"""Process-wide cache of jitted executables keyed by structural signature,
with an optional PERSISTENT tier of AOT-serialized executables.

Physical plans are rebuilt per query, so per-instance ``jax.jit(bound
method)`` would recompile the same XLA program on every run — the dominant
cost for repeated queries (an aggregate stage costs seconds to compile,
microseconds to run).  The reference relies on cudf's precompiled kernels;
the TPU analog is this cache: executables are shared across plan instances
whose expression forests are structurally identical (``Expression.
cache_key`` includes literal values, so constants bake correctly).

The cached callable still goes through jax.jit's own shape-bucket cache, so
one signature may hold several XLA executables (one per input capacity).

Thread safety: the pipeline driver (exec/pipeline.py) and concurrent
sessions hit the cache from multiple threads, so every map access holds
``_LOCK``.  A signature MISS serializes builders through a per-signature
build lock: concurrent queries racing into the same new signature share ONE
``jax.jit`` wrapper (and therefore one trace/compile on first call) instead
of building N duplicates with first-insert-wins.

Persistent tier (``spark.rapids.tpu.jitCache.dir``): on the first call of a
(signature, input-shapes) pair the cache consults an on-disk store of
AOT-lowered executables serialized via ``jax.export`` — a warm hit
deserializes the StableHLO module and skips Python tracing entirely (the
dominant repeat-query cost); a miss traces once, then exports and persists
the module so the NEXT process compiles nothing.  Entries are keyed by
sha256 over (structural signature, input avals, backend, jax/jaxlib
versions) — the same full-width-digest discipline as the PR5 checkpoint
``stage_id`` (a colliding key would run the wrong program; the payload CRC
cannot catch that).  Safety: every load verifies a crc32 over the payload
and the recorded environment header; truncation, bit rot
(``jitcache.load`` fire_mutate chaos hook), or a store written by a
different jax/jaxlib falls back to a fresh trace+compile — the entry is
dropped with a ``JitCacheInvalid`` event, never a failed or wrong query.
Cold-path execution always runs the canonical in-process jit (donation
semantics preserved); only warm starts route through the deserialized
module.

Donation: callers pass ``jit_kwargs`` (e.g. ``donate_argnums``) through to
``jax.jit``; anything that changes the compiled program MUST be part of
``signature`` (stage compilers fold their donation flag in — see
ops/compiler.py).
"""

from __future__ import annotations

import functools
import hashlib
import json
import os
import re
import threading
import zlib
from collections import OrderedDict
from typing import Any, Callable, Dict, Hashable, List, Optional, Tuple

import jax

from spark_rapids_tpu.utils import tracing

# LRU-bounded: cached entries close over their originating plan instance
# (and thus its child tree), so an unbounded map would pin every distinct
# query shape ever run.  256 signatures comfortably covers a working set
# of queries while keeping retention bounded.
_MAX_ENTRIES = 256
_CACHE: "OrderedDict[Hashable, _Entry]" = OrderedDict()
_LOCK = threading.Lock()
_BUILD_LOCKS: Dict[Hashable, threading.Lock] = {}
_HITS = 0
_MISSES = 0
# dispatches of entries the LRU already evicted (live entries carry
# their own per-entry counter — no global lock on the dispatch path)
_EVICTED_DISPATCHES = 0

# ANSI check messages recorded at trace time by the stage compilers
# (ops/compiler.py aliases this as _CHECK_MSGS).  Living here lets the
# persistent tier serialize them into entry headers, so a warm start
# that never traces still raises the exact ANSI message.
STAGE_CHECKS: Dict[Hashable, List[str]] = {}

_MAGIC = "srtpu-jit"
_FORMAT_VERSION = 1


def _shape_key(args) -> Tuple:
    """Aval bucket of one call: pytree structure plus per-leaf
    (dtype, shape, weak) — what jax.jit's own shape cache keys on."""
    leaves, treedef = jax.tree_util.tree_flatten(args)
    parts = []
    for leaf in leaves:
        shape = getattr(leaf, "shape", None)
        dt = getattr(leaf, "dtype", None)
        if shape is not None and dt is not None:
            parts.append((str(dt), tuple(int(s) for s in shape),
                          bool(getattr(leaf, "weak_type", False))))
        else:
            parts.append(("py", type(leaf).__name__))
    return (str(treedef), tuple(parts))


class PersistentJitCache:
    """On-disk store of ``jax.export``-serialized executables.

    One file per (signature, shapes) pair: a JSON header line (magic,
    environment, payload crc32, recorded ANSI check messages) followed
    by the serialized module.  Writes are atomic (temp + os.replace);
    reads verify environment and checksum and NEVER raise into the
    query — any problem degrades to a fresh compile."""

    def __init__(self, dirpath: str, max_bytes: int = 1 << 30):
        self.dir = dirpath
        self.max_bytes = max_bytes
        os.makedirs(dirpath, exist_ok=True)
        self._lock = threading.Lock()
        self.counters: Dict[str, int] = {
            "hits": 0, "misses": 0, "invalid": 0, "stores": 0,
            "storeErrors": 0, "bytesWritten": 0}

    def _bump(self, field: str, by: int = 1) -> None:
        with self._lock:
            self.counters[field] += int(by)

    def snapshot(self) -> Dict[str, int]:
        with self._lock:
            out = dict(self.counters)
        out["dir"] = self.dir
        return out

    @staticmethod
    def _env() -> Dict[str, str]:
        import jaxlib
        return {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
                "backend": jax.default_backend(),
                "fmt": _FORMAT_VERSION}

    def _path(self, sig, shape_key) -> str:
        # full-width sha256 (the checkpoint.stage_id discipline): a key
        # collision would execute the WRONG program's valid bytes — the
        # one failure the payload checksum cannot catch
        digest = hashlib.sha256(
            repr((sig, shape_key,
                  sorted(self._env().items()))).encode()).hexdigest()
        return os.path.join(self.dir, f"{digest}.jit")

    # ------------------------------------------------------------- load --
    def load(self, sig, shape_key):
        """Deserialized ``jax.export.Exported`` for the pair, or None
        (miss / invalid — the caller compiles fresh either way)."""
        from spark_rapids_tpu.robustness.faults import TimeoutFault
        from spark_rapids_tpu.robustness.inject import (fire, fire_mutate)
        path = self._path(sig, shape_key)
        try:
            fire("jitcache.load")
            if not os.path.exists(path):
                self._bump("misses")
                return None
            with open(path, "rb") as f:
                raw = f.read()
            head, sep, payload = raw.partition(b"\n")
            if not sep:
                raise ValueError("truncated header")
            header = json.loads(head.decode("utf-8"))
            if header.get("magic") != _MAGIC:
                raise ValueError("bad magic")
            if header.get("env") != self._env():
                self._invalid(path, "env-mismatch: entry written by "
                                    f"{header.get('env')}")
                return None
            # chaos hook: offer the payload to an armed corrupt rule so
            # the CRC gate has real rot to catch (checkpoint.restore
            # discipline); raise/delay rules also apply here
            payload = fire_mutate("jitcache.load", payload)
            if len(payload) != header.get("len") or \
                    zlib.crc32(payload) != header.get("crc"):
                self._invalid(path, "crc/length mismatch")
                return None
            from jax import export as jexport
            exported = jexport.deserialize(bytearray(payload))
            checks = header.get("checks")
            if checks is not None:
                STAGE_CHECKS[sig] = list(checks)
            self._bump("hits")
            return exported
        except TimeoutFault:
            raise  # watchdog cancellation at the fire() checkpoint
        except Exception as e:  # noqa: BLE001 - degrade, never fail
            self._invalid(path, f"{type(e).__name__}: {e}")
            return None

    def _invalid(self, path: str, reason: str) -> None:
        """Drop an unusable entry: unlink, count, event — the caller
        falls back to a fresh compile (also counted as a miss: the
        warm-start acceptance pins misses, and an invalid entry DID
        cost a compile)."""
        try:
            os.unlink(path)
        except OSError:
            pass
        self._bump("invalid")
        self._bump("misses")
        try:
            from spark_rapids_tpu.utils.events import emit_on_session
            emit_on_session("JitCacheInvalid", reason=reason,
                            entry=os.path.basename(path))
        except Exception:
            pass  # observability must never mask the degraded load

    # ------------------------------------------------------------ store --
    def store(self, sig, shape_key, jitted, args) -> None:
        """AOT-export the traced program for ``args`` and persist it.
        Best-effort: anything unexportable (exotic primitives, device
        contexts jax.export cannot describe) just skips persistence."""
        try:
            from jax import export as jexport
            exported = jexport.export(jitted)(*args)
            payload = exported.serialize()
            header = {"magic": _MAGIC, "env": self._env(),
                      "crc": zlib.crc32(bytes(payload)),
                      "len": len(payload),
                      # export already traced the function, so trace-
                      # time ANSI messages exist by now
                      "checks": STAGE_CHECKS.get(sig)}
            path = self._path(sig, shape_key)
            tmp = path + ".tmp"
            with open(tmp, "wb") as f:
                f.write(json.dumps(header).encode("utf-8"))
                f.write(b"\n")
                f.write(bytes(payload))
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, path)
            self._bump("stores")
            self._bump("bytesWritten", len(payload))
            self._prune()
        except Exception:  # noqa: BLE001 - persistence is an optimization
            self._bump("storeErrors")

    def _prune(self) -> None:
        """Oldest-first eviction keeps the store under ``max_bytes``
        (the checkpoint maxBytes discipline)."""
        try:
            entries = []
            total = 0
            with os.scandir(self.dir) as it:
                for de in it:
                    if de.name.endswith(".jit"):
                        st = de.stat()
                        entries.append((st.st_mtime, st.st_size, de.path))
                        total += st.st_size
            entries.sort()
            while total > self.max_bytes and entries:
                _, size, path = entries.pop(0)
                try:
                    os.unlink(path)
                    total -= size
                except OSError:
                    break
        except OSError:
            pass


_TIER: Optional[PersistentJitCache] = None


def configure_persistent(dirpath: Optional[str],
                         max_bytes: int = 1 << 30) -> None:
    """Enable (or disable, dirpath=None) the persistent tier.  Called at
    session construction from ``spark.rapids.tpu.jitCache.dir``; the
    tier is process-global (the in-memory cache it backs is too).  A
    dir change resets every live entry's shape bindings so already-
    cached signatures re-consult the new store on their next call."""
    global _TIER
    with _LOCK:
        cur_dir = _TIER.dir if _TIER is not None else None
        new_dir = dirpath or None
        if new_dir == cur_dir:
            if _TIER is not None:
                _TIER.max_bytes = max_bytes
            return
        _TIER = PersistentJitCache(new_dir, max_bytes) \
            if new_dir else None
        entries = list(_CACHE.values())
    for e in entries:
        e.rebind()


def persistent_info() -> Dict[str, Any]:
    """Persistent-tier counters (zeros + enabled=False when off)."""
    tier = _TIER
    if tier is None:
        return {"enabled": False, "hits": 0, "misses": 0, "invalid": 0,
                "stores": 0, "storeErrors": 0, "bytesWritten": 0}
    out = tier.snapshot()
    out["enabled"] = True
    return out


class _Entry:
    """The callable ``cached_jit`` returns: counts dispatches and binds
    each input-shape bucket to either the in-process jitted function or
    a warm executable deserialized from the persistent tier."""

    __slots__ = ("sig", "_jit", "_bound", "_lock", "dispatches",
                 "_cold")

    def __init__(self, sig, jitted):
        self.sig = sig
        self._jit = jitted
        self._bound: Dict[Tuple, Callable] = {}
        self._lock = threading.Lock()
        self.dispatches = 0
        self._cold = True  # first dispatch = trace+compile (span site)

    def rebind(self) -> None:
        with self._lock:
            self._bound = {}

    def __call__(self, *args):
        # unlocked bump: a launch counter for tests/observability —
        # losing a rare racing increment beats serializing every
        # dispatch in the process on one mutex
        self.dispatches += 1
        # the entry's first dispatch pays the Python trace + XLA
        # compile (or the AOT deserialize): ``jit.trace`` spans it and
        # feeds the site's compile_ms observation; every later one is
        # a ``jit.dispatch`` (later shape-bucket recompiles, rare, ride
        # inside it).  Both carry the site and the operator whose pull
        # launched it, so the rollup maps program -> site -> operator.
        # The flag flips even when tracing is off so arming
        # mid-process never mis-labels a warm site; warm dispatches
        # with tracing off stay a single branch.
        cold, self._cold = self._cold, False
        if tracing._active:
            with tracing.span("jit.trace" if cold else "jit.dispatch",
                              site=self.sig, op=tracing.current_op(),
                              observe="compile_ms" if cold else None):
                return self._dispatch(args)
        return self._dispatch(args)

    def _dispatch(self, args):
        tier = _TIER
        if tier is None:
            return self._jit(*args)
        key = _shape_key(args)
        fn = self._bound.get(key)
        if fn is None:
            fn = self._bind(key, args, tier)
        return fn(*args)

    def _bind(self, key, args, tier: PersistentJitCache) -> Callable:
        store = False
        with self._lock:
            fn = self._bound.get(key)
            if fn is None:
                with tracing.span("jit.aotLoad", site=self.sig):
                    exported = tier.load(self.sig, key)
                if exported is not None:
                    fn = self._guarded(key, jax.jit(_named(
                        exported.call, program_name(self.sig))))
                else:
                    # miss: execution stays on the canonical jit
                    # (donation semantics preserved); the module is
                    # exported below so the NEXT process skips tracing
                    fn = self._jit
                    store = True
                self._bound[key] = fn
        if store:
            # outside the entry lock: export performs its own trace
            # (jax.export cannot reuse the jit call's lowering), so a
            # cold run with the tier on pays the Python trace twice —
            # the documented price of a zero-trace warm start; holding
            # the lock here would also stall concurrent dispatches
            with tracing.span("jit.aotStore", site=self.sig):
                tier.store(self.sig, key, self._jit, args)
        return fn

    def _guarded(self, key, loaded: Callable) -> Callable:
        """First call through a deserialized executable is guarded: an
        export that cannot run in this context (the device set moved
        between save and use) falls back to a fresh trace/compile —
        a degraded load must never fail the query.  Device kernels
        raise no data-dependent Python exceptions (ANSI checks travel
        as output flags), so a first-call exception here can only be a
        binding problem; the fallback re-runs the same computation."""
        ok: List[bool] = []

        def run(*args):
            if ok:
                return loaded(*args)
            try:
                out = loaded(*args)
            except Exception as e:  # noqa: BLE001 - see docstring
                # genuine runtime faults the recovery stack owns must
                # propagate: device OOM belongs to the retry ladder and
                # a watchdog cancellation to the driver — neither means
                # the ENTRY is bad (re-tracing under the same memory
                # pressure would just OOM again, minus one cache entry)
                from spark_rapids_tpu.memory.retry import is_oom
                from spark_rapids_tpu.robustness.faults import \
                    TimeoutFault
                if isinstance(e, TimeoutFault) or is_oom(e):
                    raise
                tier = _TIER
                if tier is not None:
                    tier._invalid(tier._path(self.sig, key),
                                  "deserialized executable failed to "
                                  "bind in this process")
                with self._lock:
                    self._bound[key] = self._jit
                return self._jit(*args)
            ok.append(True)
            return out

        return run


def program_name(signature: Hashable) -> str:
    """The name a signature's program carries in the profiler's trace
    (``jit_<this>``): the signature's kind — its first string — and the
    first 8 hex of the site id the spans rollup and the observation
    store key on."""
    parts = signature if isinstance(signature, tuple) else (signature,)
    kind = next((p for p in parts if isinstance(p, str)), "program")
    kind = re.sub(r"\W+", "_", kind).strip("_") or "program"
    return f"{kind}_{tracing.site_id(signature)[:8]}"


def _named(fn: Callable, name: str) -> Callable:
    """``fn`` under ``name``: jax.jit names the XLA module after the
    function it is handed, and a bound method or a shard_map closure
    cannot be renamed in place.  Runs at trace time only."""
    @functools.wraps(fn)
    def call(*args, **kwargs):
        return fn(*args, **kwargs)
    call.__name__ = call.__qualname__ = name
    return call


def cached_jit(signature: Hashable, make: Callable[[], Callable],
               **jit_kwargs: Any) -> Callable:
    """Return a jitted callable for ``signature``; build via ``make()``
    on miss.  ``make`` returns the plain (untraced) function to jit — it
    is only invoked when the signature is new (exactly once even under a
    thread race: builders serialize on a per-signature lock), so
    closures over a freshly constructed plan instance are safe as long
    as everything the function's trace depends on is captured in the
    signature."""
    global _HITS, _MISSES
    with _LOCK:
        fn = _CACHE.get(signature)
        if fn is not None:
            _CACHE.move_to_end(signature)
            _HITS += 1
            return fn
        build_lock = _BUILD_LOCKS.setdefault(signature, threading.Lock())
    with build_lock:
        with _LOCK:
            fn = _CACHE.get(signature)
            if fn is not None:
                # a racing builder finished while we waited: share its
                # executable (its jit shape-cache is what every thread
                # must hit)
                _CACHE.move_to_end(signature)
                _HITS += 1
                return fn
        built = _Entry(signature, jax.jit(
            _named(make(), program_name(signature)), **jit_kwargs))
        with _LOCK:
            global _EVICTED_DISPATCHES
            _MISSES += 1
            _CACHE[signature] = built
            _BUILD_LOCKS.pop(signature, None)
            while len(_CACHE) > _MAX_ENTRIES:
                old_sig, old = _CACHE.popitem(last=False)
                _EVICTED_DISPATCHES += old.dispatches
                # the trace-time ANSI messages die with the entry, or
                # STAGE_CHECKS would leak one list per evicted shape
                STAGE_CHECKS.pop(old_sig, None)
    return built


def cache_info() -> Dict[str, int]:
    with _LOCK:
        return {"entries": len(_CACHE), "hits": _HITS, "misses": _MISSES}


def dispatch_count() -> int:
    """Total calls through cached executables — the launch counter the
    fusion tests pin (one fused stage = one dispatch per batch)."""
    with _LOCK:
        return _EVICTED_DISPATCHES + sum(e.dispatches
                                         for e in _CACHE.values())


def clear() -> None:
    global _HITS, _MISSES, _EVICTED_DISPATCHES
    with _LOCK:
        # dispatch totals survive: tests pin DELTAS across clear()s
        _EVICTED_DISPATCHES += sum(e.dispatches
                                   for e in _CACHE.values())
        _CACHE.clear()
        _BUILD_LOCKS.clear()
        STAGE_CHECKS.clear()
        _HITS = 0
        _MISSES = 0
