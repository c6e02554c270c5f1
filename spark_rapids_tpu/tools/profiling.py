"""Profiling tool: aggregate per-op metrics, plan graphs, health checks.

CLI over engine event logs — the role of the reference's profiling tool
(tools/src/main/.../profiling/ProfileMain.scala: CollectInformation,
Analysis, HealthCheck, GenerateDot): per-operator time/row aggregation
across queries, the slowest queries, spill totals, query-duration skew,
a DOT graph of any query's physical plan, and a health check listing
failures.

Usage:  python -m spark_rapids_tpu.tools.profiling LOGDIR
            [--dot QUERYID] [--top N]
"""

from __future__ import annotations

import argparse
import statistics
import sys
from collections import defaultdict
from typing import Dict, List, Tuple

from spark_rapids_tpu.tools.eventlog import AppInfo, QueryInfo, load_logs


def aggregate_ops(apps: List[AppInfo]) -> List[Tuple[str, float, int, int]]:
    """[(op_name, total opTime ms, total rows, occurrences)] sorted by
    time desc."""
    time_ns: Dict[str, int] = defaultdict(int)
    rows: Dict[str, int] = defaultdict(int)
    count: Dict[str, int] = defaultdict(int)
    for app in apps:
        for q in app.queries:
            for path, m in q.metrics.items():
                name = path.rsplit(".", 1)[-1]
                time_ns[name] += m.get("opTimeSelf", m.get("opTime", 0))
                rows[name] += m.get("numOutputRows", 0)
                count[name] += 1
    out = [(n, time_ns[n] / 1e6, rows[n], count[n]) for n in time_ns]
    out.sort(key=lambda t: -t[1])
    return out


def slowest_queries(apps: List[AppInfo], top: int
                    ) -> List[Tuple[str, QueryInfo]]:
    pairs = [(a.session_id, q) for a in apps for q in a.queries]
    pairs.sort(key=lambda p: -p[1].duration_ms)
    return pairs[:top]


def skew_stats(apps: List[AppInfo]) -> Dict[str, float]:
    durs = [q.duration_ms for a in apps for q in a.queries if q.succeeded]
    if not durs:
        return {}
    mean = statistics.fmean(durs)
    return {
        "queries": len(durs),
        "mean_ms": mean,
        "p50_ms": statistics.median(durs),
        "max_ms": max(durs),
        "skew_ratio": (max(durs) / mean) if mean else 0.0,
    }


def pipeline_stats(apps: List[AppInfo]) -> Dict[str, float]:
    """Aggregate async-pipeline effectiveness across queries: mean fill
    ratio (batch-weighted), total host syncs, overlap time, and jit
    cache hit rate (ops/jit_cache.py counters)."""
    fill_w, batches, syncs, overlap_ms = 0.0, 0, 0, 0.0
    hits, misses, piped = 0, 0, 0
    for a in apps:
        for q in a.queries:
            p = q.pipeline
            if not p:
                continue
            piped += 1
            b = p.get("batches", 0)
            fill_w += p.get("pipelineFillRatio", 0.0) * b
            batches += b
            syncs += p.get("hostSyncCount", 0)
            overlap_ms += p.get("uploadOverlapMs", 0.0)
            hits += p.get("jitCacheHits", 0)
            misses += p.get("jitCacheMisses", 0)
    if not piped:
        return {}
    return {
        "queries": piped,
        "batches": batches,
        "fill_ratio": (fill_w / batches) if batches else 0.0,
        "host_sync_count": syncs,
        "upload_overlap_ms": overlap_ms,
        "jit_cache_hits": hits,
        "jit_cache_misses": misses,
    }


def shuffle_wire_stats(apps: List[AppInfo]) -> Dict[str, float]:
    """Aggregate shuffle-wire effectiveness across distributed queries:
    exchanges, collectives launched, bytes moved and the overall
    padding ratio (wire rows / useful rows — 1.0 is a perfectly dense
    exchange; numShards is full-capacity padding)."""
    exchanged, exch, coll, moved, useful, bytes_, ovf, fb = \
        0, 0, 0, 0, 0, 0, 0, 0
    overlap_ms, wall_ms, async_n, ragged_n, staged_b = 0.0, 0.0, 0, 0, 0
    enc_saved, dict_b, enc_decoded, dict_fb = 0, 0, 0, 0
    for a in apps:
        for q in a.queries:
            s = q.shuffle
            if not s or not s.get("exchanges"):
                continue
            exchanged += 1
            exch += s.get("exchanges", 0)
            coll += s.get("collectives", 0)
            moved += s.get("rowsMoved", 0)
            useful += s.get("rowsUseful", 0)
            bytes_ += s.get("bytesMoved", 0)
            ovf += s.get("slotOverflowRetries", 0)
            fb += s.get("perColumnFallbacks", 0)
            overlap_ms += s.get("exchangeOverlapMs", 0.0)
            wall_ms += s.get("exchangeWallMs", 0.0)
            async_n += s.get("asyncExchanges", 0)
            ragged_n += s.get("raggedExchanges", 0)
            staged_b += s.get("hostStagedBytes", 0)
            enc_saved += s.get("encodedBytesSaved", 0)
            dict_b += s.get("wireDictBytes", 0)
            enc_decoded += s.get("encodableDecodedExchanges", 0)
            dict_fb += s.get("wireDictFallbacks", 0)
    if not exchanged:
        return {}
    return {
        "queries": exchanged,
        "exchanges": exch,
        "collectives": coll,
        "bytes_moved": bytes_,
        "padding_ratio": moved / max(useful, 1),
        "slot_overflow_retries": ovf,
        "per_column_fallbacks": fb,
        # compressed wire (encoding.wire.enabled): bytes the code
        # narrowing shaved plus the dictionary-delta broadcast cost
        "encoded_bytes_saved": enc_saved,
        "wire_dict_bytes": dict_b,
        "wire_dict_fallbacks": dict_fb,
        "encodable_decoded_exchanges": enc_decoded,
        # async exchange/compute overlap (parallel/exchange_async.py):
        # overlap_fraction is the headline — how much of the exchange
        # tail the host spent dispatching downstream work instead of
        # blocking on verification
        "exchange_overlap_ms": round(overlap_ms, 3),
        "exchange_wall_ms": round(wall_ms, 3),
        "overlap_fraction": round(overlap_ms / wall_ms, 3)
        if wall_ms else 0.0,
        "async_exchanges": async_n,
        "ragged_exchanges": ragged_n,
        "host_staged_bytes": staged_b,
    }


def checkpoint_stats(apps: List[AppInfo]) -> Dict[str, float]:
    """Aggregate stage-checkpoint effectiveness across queries: writes
    and bytes persisted, resumes and the exchange stages they skipped,
    evictions and invalidations (robustness/checkpoint.py)."""
    writes = bytes_ = resumes = skipped = evicts = invalid = 0
    touched = 0
    for a in apps:
        events = list(a.checkpoint) + [c for q in a.queries
                                       for c in q.checkpoint]
        if not events:
            continue
        touched += 1
        for c in events:
            kind = c.get("kind")
            if kind == "write":
                writes += 1
                bytes_ += c.get("bytes", 0)
            elif kind == "resume":
                resumes += 1
                skipped += c.get("stagesSaved", 0)
            elif kind == "evict":
                evicts += 1
            elif kind == "invalid":
                invalid += 1
    if not touched:
        return {}
    return {
        "writes": writes,
        "bytes_written": bytes_,
        "resumes": resumes,
        "stages_skipped": skipped,
        "evictions": evicts,
        "invalidations": invalid,
    }


def incremental_stats(apps: List[AppInfo]) -> Dict[str, object]:
    """Continuous-ingest effectiveness across sessions
    (robustness/incremental.py): committed epochs split by mode
    (incremental vs full-recompute), rollbacks, state evictions,
    lineage-splice resumes, and the standing state's last committed
    size.  ``reuse_ratio`` is the headline: the fraction of ticks that
    actually rode the committed epoch instead of recomputing."""
    commits = inc = full = rollbacks = evicts = resumes = 0
    state_bytes = 0
    watermarks: Dict[object, int] = {}  # per standing query (store id)
    wm_buckets = wm_bytes = 0
    sink_commits = sink_replays = 0
    rounds = round_pulls = round_splices = round_failures = 0
    for a in apps:
        events = list(a.incremental) + [e for q in a.queries
                                        for e in q.incremental]
        for e in events:
            kind = e.get("kind")
            if kind == "commit":
                commits += 1
                if e.get("mode") == "incremental" or e.get("reusedState"):
                    inc += 1
                else:
                    full += 1
                state_bytes = e.get("stateBytes", state_bytes)
            elif kind == "rollback":
                rollbacks += 1
            elif kind == "evict":
                evicts += 1
            elif kind == "resume":
                resumes += 1
            elif kind == "watermark":
                if e.get("watermark") is not None:
                    watermarks[e.get("store")] = e["watermark"]
                wm_buckets += e.get("evictedBuckets", 0)
                wm_bytes += e.get("evictedBytes", 0)
            elif kind == "sink":
                if e.get("replayed"):
                    sink_replays += 1
                else:
                    sink_commits += 1
            elif kind == "round":
                rounds += 1
                round_pulls += e.get("sourcePulls", 0)
                round_splices += e.get("splices", 0)
                round_failures += e.get("failures", 0)
    if not commits and not rollbacks and not rounds:
        return {}
    return {
        "commits": commits,
        "incremental_ticks": inc,
        "full_recomputes": full,
        "rollbacks": rollbacks,
        "state_evictions": evicts,
        "splice_resumes": resumes,
        "state_bytes": state_bytes,
        "reuse_ratio": inc / commits if commits else 0.0,
        # windowed shapes: where each standing query's event-time
        # watermark last landed ({store id: watermark} — one pooled
        # number would show whichever query committed last) and what
        # eviction reclaimed across all committed epochs
        "watermark": watermarks or None,
        "watermark_evicted_buckets": wm_buckets,
        "watermark_evicted_bytes": wm_bytes,
        # exactly-once sinks: NEW committed emissions vs idempotent
        # re-emissions of an already-committed epoch
        "sink_commits": sink_commits,
        "sink_replays": sink_replays,
        # fleet rounds: shared-ingest fan-out effectiveness
        "fleet_rounds": rounds,
        "fleet_source_pulls": round_pulls,
        "fleet_splices": round_splices,
        "fleet_failures": round_failures,
    }


def sharing_stats(apps: List[AppInfo]) -> Dict[str, float]:
    """Cross-query reuse effectiveness across sessions
    (serving/reuse.py + serving/scheduler.py): result-cache
    hits/misses/stores/invalidations, shared stage-store
    writes/splices, and the fair interleaver's wait/timeslice
    accounting."""
    hits = misses = stores = invalid = evicts = 0
    t_hits = t_misses = t_stores = 0
    writes = splices = 0
    interleaved = 0
    wait_ms = slices = 0.0
    for a in apps:
        events = list(a.sharing_events) + \
            [e for q in a.queries for e in q.sharing_events]
        for e in events:
            kind, store = e.get("kind"), e.get("store")
            if store == "result":
                if kind == "hit":
                    hits += 1
                elif kind == "store":
                    stores += 1
                elif kind == "invalid":
                    invalid += 1
                elif kind == "evict":
                    evicts += 1
            elif store == "template":
                if kind == "hit":
                    t_hits += 1
                elif kind == "store":
                    t_stores += 1
            else:
                if kind == "write":
                    writes += 1
                elif kind == "splice":
                    splices += 1
                elif kind == "invalid":
                    invalid += 1
                elif kind == "evict":
                    evicts += 1
        for q in a.queries:
            sh = q.sharing
            if not sh:
                continue
            if sh.get("resultCache") == "miss" or \
                    sh.get("resultCache") == "invalidated":
                misses += 1
            if sh.get("templateCache") == "miss" or \
                    sh.get("templateCache") == "invalidated":
                t_misses += 1
            il = sh.get("interleave")
            if il:
                interleaved += 1
                wait_ms += il.get("waitMs", 0.0)
                slices += il.get("timeslices", 0)
    if not (hits or misses or stores or writes or splices or
            interleaved or invalid or evicts or
            t_hits or t_misses or t_stores):
        return {}
    return {
        "result_cache_hits": hits,
        "result_cache_misses": misses,
        "result_cache_stores": stores,
        "template_cache_hits": t_hits,
        "template_cache_misses": t_misses,
        "template_cache_stores": t_stores,
        "stage_writes": writes,
        "stage_splices": splices,
        "invalidations": invalid,
        "evictions": evicts,
        "interleaved_queries": interleaved,
        "interleave_wait_ms": wait_ms,
        "timeslices": slices,
    }


def planner_stats(apps: List[AppInfo]) -> Dict[str, object]:
    """Self-tuning cost-model effectiveness across queries
    (plan/costmodel.py QueryEnd ``planner`` dicts): decisions per
    knob, how many were evidence-fed vs built-in vs conf-overridden,
    plus the replan/mispredict/degraded-load tallies the health
    checks key on.  Empty when no query carried a planner dict
    (costModel.enabled off)."""
    queries = decisions = evidence = overrides = 0
    replans = mispredicts = 0
    invalid = 0
    by_knob: Dict[str, int] = {}
    chosen: Dict[str, int] = {}
    for a in apps:
        invalid += len(a.costmodel)
        for q in a.queries:
            invalid += len(q.costmodel)
            p = q.planner
            if not p:
                continue
            queries += 1
            replans += int(p.get("replans", 0))
            mispredicts += int(p.get("mispredicts", 0))
            for d in p.get("decisions", []):
                decisions += 1
                by_knob[d.get("knob", "?")] = \
                    by_knob.get(d.get("knob", "?"), 0) + 1
                if d.get("knob") == "exchange":
                    chosen[d.get("chosen", "?")] = \
                        chosen.get(d.get("chosen", "?"), 0) + 1
                if d.get("evidence"):
                    evidence += 1
                if d.get("override"):
                    overrides += 1
    if not queries and not invalid:
        return {}
    return {
        "queries": queries,
        "decisions": decisions,
        "evidence_decisions": evidence,
        "override_decisions": overrides,
        "by_knob": dict(sorted(by_knob.items())),
        "exchange_modes": dict(sorted(chosen.items())),
        "replans": replans,
        "mispredicts": mispredicts,
        "invalid_loads": invalid,
    }


def fusion_stats(apps: List[AppInfo]) -> Dict[str, float]:
    """Whole-stage fusion + persistent jit-cache effectiveness across
    queries (exec/fusion.py, ops/jit_cache.py): stages/operators fused,
    jit dispatches saved, chains that COULD have fused but ran unfused,
    and the persistent tier's warm-start hit rate."""
    touched = stages = ops = saved = chains = encoded = 0
    phits = pmisses = pinvalid = pstores = 0
    for a in apps:
        for q in a.queries:
            fu = q.fusion
            if not fu:
                continue
            touched += 1
            stages += fu.get("fusedStages", 0)
            ops += fu.get("fusedOperators", 0)
            saved += fu.get("dispatchesSaved", 0)
            chains += fu.get("fusibleChains", 0)
            encoded += fu.get("encodedStages", 0)
            phits += fu.get("persistentHits", 0)
            pmisses += fu.get("persistentMisses", 0)
            pinvalid += fu.get("persistentInvalid", 0)
            pstores += fu.get("persistentStores", 0)
    if not touched:
        return {}
    return {
        "queries": touched,
        "fused_stages": stages,
        "fused_operators": ops,
        "dispatches_saved": saved,
        "fusible_chains": chains,
        "encoded_stages": encoded,
        "persistent_hits": phits,
        "persistent_misses": pmisses,
        "persistent_invalid": pinvalid,
        "persistent_stores": pstores,
    }


def span_stats(apps: List[AppInfo]) -> Dict[str, object]:
    """"Where the time went": aggregate the span rollups (QueryEnd
    ``spans`` dicts, utils/tracing.py) across traced queries — wall vs
    attributed exclusive time, the phase stripes, and the top span
    points by exclusive time.  ``unattributed_frac`` is the headline
    health metric: wall the taxonomy never covered."""
    traced = 0
    wall = excl = unattr = overlap = 0.0
    phases: Dict[str, float] = defaultdict(float)
    points: Dict[str, float] = defaultdict(float)
    for a in apps:
        for q in a.queries:
            sp = q.spans
            if not sp or not sp.get("events"):
                continue
            traced += 1
            wall += sp.get("wallMs", 0.0)
            excl += sp.get("exclusiveMs", 0.0)
            unattr += sp.get("unattributedMs", 0.0)
            overlap += sp.get("overlapMs", 0.0)
            for ph, ms in (sp.get("phases") or {}).items():
                phases[ph] += ms
            for pt, v in (sp.get("points") or {}).items():
                points[pt] += v.get("exclusiveMs", 0.0)
    if not traced:
        return {}
    return {
        "queries": traced,
        "wall_ms": round(wall, 3),
        "exclusive_ms": round(excl, 3),
        "unattributed_ms": round(unattr, 3),
        "unattributed_frac": round(unattr / wall, 4) if wall else 0.0,
        "overlap_ms": round(overlap, 3),
        "phases": {k: round(v, 3) for k, v in sorted(phases.items())},
        "top_points": sorted(points.items(), key=lambda kv: -kv[1]),
    }


# a query whose spans cover less than this fraction of its wall is an
# instrumentation blind spot — the health check that keeps future
# instrumentation honest (ISSUE 12 contract: wall - sum(exclusive)
# > 20% flags)
UNATTRIBUTED_FRAC_LIMIT = 0.20
# ignore sub-5ms envelopes: fixed per-query overheads (planning,
# envelope bookkeeping) legitimately dominate trivial queries
_UNATTRIBUTED_MIN_WALL_MS = 5.0


def site_history(obs_dir: str, top: int = 20) -> str:
    """Per-site observation history (utils/tracing.ObservationStore):
    the persisted evidence the self-tuning planner will consume —
    rendered so a human can consume it first."""
    from spark_rapids_tpu.utils.tracing import ObservationStore
    records = ObservationStore.read(obs_dir)
    if not records:
        return f"no observation store under {obs_dir}"
    out = [f"-- Per-site observation history ({obs_dir}) --",
           f"{'site':18s} {'n':>5s} {'rows':>10s} {'bytes':>12s} "
           f"{'skew':>6s} {'compile_ms':>10s} {'overlap_ms':>10s} "
           f"{'span_ms':>9s}"]
    ranked = sorted(records.items(),
                    key=lambda kv: -kv[1].get("span_ms", 0.0))
    for sid, r in ranked[:top]:
        out.append(
            f"{sid:18s} {int(r.get('n', 0)):5d} "
            f"{int(r.get('rows', 0)):10d} {int(r.get('bytes', 0)):12d} "
            f"{r.get('skew', 0.0):6.3f} {r.get('compile_ms', 0.0):10.1f} "
            f"{r.get('overlap_ms', 0.0):10.1f} "
            f"{r.get('span_ms', 0.0):9.1f}")
    if len(ranked) > top:
        out.append(f"  ... {len(ranked) - top} more site(s)")
    return "\n".join(out)


def nearest_rank(sorted_vals: List[float], p: float) -> float:
    """Nearest-rank percentile over an ascending list."""
    if not sorted_vals:
        return 0.0
    return sorted_vals[min(int(p * len(sorted_vals)),
                           len(sorted_vals) - 1)]


def concurrency_stats(apps: List[AppInfo]) -> Dict[str, float]:
    """Serving-layer concurrency report: peak simultaneously-open
    query envelopes per the event timeline, admission grants/waits and
    typed rejections, and budget-ladder activity — the observability
    face of the admission semaphore (serving/admission.py)."""
    grants = rejects = budget_events = 0
    wait_ms = 0.0
    waits: List[float] = []
    peak = 0
    for a in apps:
        peak = max(peak, a.max_concurrent())
        grants += len(a.admission)
        rejects += len(a.rejections)
        budget_events += len(a.budget)
        # one wait sample per admitted query: the grant events are the
        # complete population (every admission emits one, whether or
        # not the query later reaches QueryEnd); the per-query
        # QueryEnd dicts restate the same waits, so counting both
        # would double the percentile multiset
        for g in a.admission:
            w = g.get("waitMs", 0.0)
            wait_ms += w
            waits.append(w)
        if not a.admission:
            for q in a.queries:
                if q.admission:
                    w = q.admission.get("waitMs", 0.0)
                    wait_ms += w
                    waits.append(w)
        for q in a.queries:
            budget_events += len(q.budget)
    if not grants and not rejects and peak <= 1:
        return {}
    waits.sort()
    return {
        "max_concurrent": peak,
        "admitted": grants,
        "rejected": rejects,
        "total_wait_ms": round(wait_ms, 3),
        "p50_wait_ms": round(nearest_rank(waits, 0.50), 3),
        "p95_wait_ms": round(nearest_rank(waits, 0.95), 3),
        "budget_events": budget_events,
    }


def fleet_stats(apps: List[AppInfo]) -> Dict[str, object]:
    """Fleet membership report: host joins/losses, mesh shrink
    actions, and cache-fence activity (bumps and the rejected stale
    publishes the fence exists to stop) — the observability face of
    the multi-host machinery (parallel/mesh.py, serving/fleetcache.py)."""
    joins = losses = shrinks = bumps = rejections = 0
    cross_hits = 0
    suspects = recoveries = quarantines = rejoins = 0
    hedges_fired = hedges_won = dup_suppressed = 0
    hosts: set = set()
    lost_hosts: set = set()
    for a in apps:
        for ev in a.fleet:
            kind = ev.get("kind")
            if kind == "join":
                joins += 1
                hosts.add((a.session_id, ev.get("host")))
            elif kind == "loss":
                losses += 1
                lost_hosts.add((a.session_id, ev.get("host")))
            elif kind == "shrink":
                shrinks += 1
            elif kind == "fence":
                if ev.get("action") == "bump":
                    bumps += 1
                elif ev.get("action") == "reject":
                    rejections += 1
            elif kind == "suspect":
                suspects += 1
            elif kind == "recovered":
                recoveries += 1
            elif kind == "quarantine":
                quarantines += 1
            elif kind == "rejoin":
                rejoins += 1
            elif kind == "hedge_fired":
                hedges_fired += 1
            elif kind == "hedge_won":
                hedges_won += 1
        for q in a.queries:
            fh = getattr(q, "fleet_health", {}) or {}
            dup_suppressed += int(fh.get("duplicatesSuppressed", 0))
        for q in a.queries:
            for e in q.sharing_events:
                if e.get("kind") in ("hit", "splice") and \
                        e.get("tier") == "fleet" and \
                        e.get("crossProcess"):
                    cross_hits += 1
        for e in a.sharing_events:
            if e.get("kind") in ("hit", "splice") and \
                    e.get("tier") == "fleet" and e.get("crossProcess"):
                cross_hits += 1
    if not (joins or losses or shrinks or bumps or rejections
            or suspects or hedges_fired or quarantines or rejoins):
        return {}
    return {
        "hosts_seen": len(hosts),
        "joins": joins,
        "losses": losses,
        "hosts_lost": len(lost_hosts),
        "mesh_shrinks": shrinks,
        "fence_bumps": bumps,
        "fenced_publishes": rejections,
        "fleet_cross_hits": cross_hits,
        "suspects": suspects,
        "suspect_recoveries": recoveries,
        "quarantines": quarantines,
        "rejoins": rejoins,
        "hedges_fired": hedges_fired,
        "hedges_won": hedges_won,
        "duplicates_suppressed": dup_suppressed,
    }


def _fleet_problems(a: AppInfo) -> List[str]:
    """Fleet health: flapping hosts (lost then re-joined — a network
    or heartbeat-tuning problem, each flap pays a shrink/recovery),
    shrink rungs that saved nothing (the query fell through to cpu
    anyway, so the fleet paid the mesh rebuild for nothing), and
    fenced writers (the zombie-protection WORKING — worth surfacing
    because a zombie process is still running somewhere)."""
    problems: List[str] = []
    who = a.session_id
    loss_hosts: Dict[object, int] = {}
    join_after_loss: Dict[object, int] = {}
    for ev in a.fleet:
        h = ev.get("host")
        if ev.get("kind") == "loss":
            loss_hosts[h] = loss_hosts.get(h, 0) + 1
        elif ev.get("kind") == "join" and h in loss_hosts:
            join_after_loss[h] = join_after_loss.get(h, 0) + 1
    for h, flaps in sorted(join_after_loss.items()):
        problems.append(
            f"{who}: host {h} FLAPPING — declared lost then re-joined "
            f"{flaps}x; each flap pays a mesh shrink + recovery "
            "re-drive. Raise fleet.heartbeatMs/missedBeatsFatal or "
            "fix the host's network before it erodes the fleet")
    shrinks = [ev for ev in a.fleet if ev.get("kind") == "shrink"]
    if shrinks:
        # a shrink that saved nothing: some query still fell through
        # to the cpu rung (or died) after the mesh rebuild
        wasted = 0
        for q in a.queries:
            rungs = [r.get("rung") or r.get("action")
                     for r in q.recovery]
            if any(r == "shrink" for r in rungs) and (
                    any(r == "cpu" for r in rungs) or not q.succeeded):
                wasted += 1
        if wasted:
            problems.append(
                f"{who}: shrink rung saved nothing for {wasted} "
                "quer(y/ies) — the survivor mesh was rebuilt but the "
                "re-drive still fell to cpu (or failed); if this "
                "repeats, the failing stage doesn't fit the shrunken "
                "fleet and the ladder should skip straight to cpu")
    fenced = [ev for ev in a.fleet if ev.get("kind") == "fence"
              and ev.get("action") == "reject"]
    if fenced:
        eps = sorted({(ev.get("writerEpoch"), ev.get("fenceEpoch"))
                      for ev in fenced})
        problems.append(
            f"{who}: {len(fenced)} stale fleet-cache publish(es) "
            f"REJECTED by the fence (writer/fence epochs: "
            f"{', '.join(f'{w}<{f}' for w, f in eps)}) — the "
            "zombie-writer protection worked and no reader saw the "
            "entry, but a fenced-out process is still running "
            "somewhere; make sure the lost host actually died")
    # gray-failure checks: a SUSPECT verdict that never led anywhere
    # (no hedge, no quarantine, no recovery — detection without
    # mitigation is just latency), and hedges that never won (the
    # duplicate work bought nothing — the deadline fires too early or
    # the "healthy" path is just as slow)
    suspect_hosts = {ev.get("host") for ev in a.fleet
                     if ev.get("kind") == "suspect"}
    mitigated = {ev.get("host") for ev in a.fleet
                 if ev.get("kind") in ("quarantine", "recovered",
                                       "rejoin", "hedge_fired",
                                       "hedge_won")}
    for h in sorted(h for h in suspect_hosts
                    if h not in mitigated and h is not None):
        problems.append(
            f"{who}: host {h} went SUSPECT but was never mitigated — "
            "no hedge fired, no quarantine, no recovery; the fleet "
            "kept waiting on the slow host. Lower "
            "fleet.quarantineAfterMs or check the hedge-eligible "
            "paths actually ran")
    fired = sum(1 for ev in a.fleet if ev.get("kind") == "hedge_fired")
    won = sum(1 for ev in a.fleet if ev.get("kind") == "hedge_won")
    if fired and not won:
        problems.append(
            f"{who}: {fired} hedge(s) fired but ZERO won — the "
            "primary always beat the re-dispatch, so the hedging cost "
            "bought nothing; raise fleet.hedgeMarginFactor/"
            "hedgePercentile so hedges fire only on real stalls")
    return problems


def health_check(apps: List[AppInfo]) -> List[str]:
    problems = []
    for a in apps:
        for q in a.queries:
            if not q.succeeded:
                problems.append(
                    f"{a.session_id} query {q.query_id}: {q.status}")
            p = q.pipeline
            if p and p.get("batches", 0) >= 4 and \
                    p.get("pipelineFillRatio", 1.0) < 0.25:
                problems.append(
                    f"{a.session_id} query {q.query_id}: pipeline "
                    f"starved (fill ratio "
                    f"{p['pipelineFillRatio']:.2f} over "
                    f"{p['batches']} batches) — the producer is the "
                    "bottleneck; check reader threads / host decode")
            if p and p.get("batches", 0) > 0 and \
                    p.get("hostSyncCount", 0) > 4 * p["batches"]:
                problems.append(
                    f"{a.session_id} query {q.query_id}: "
                    f"{p['hostSyncCount']} host syncs over "
                    f"{p['batches']} batches — per-batch device->host "
                    "round trips serialize the pipeline "
                    "(docs/performance.md sync-point discipline)")
            sh = q.shuffle
            if sh and sh.get("exchanges"):
                pr = sh.get("paddingRatio", 0.0)
                if pr > 4.0:
                    problems.append(
                        f"{a.session_id} query {q.query_id}: shuffle "
                        f"padding ratio {pr:.1f}x over "
                        f"{sh.get('exchanges', 0)} exchange(s) — most "
                        "ICI bytes are padding; the slot planner is "
                        "oversizing (skewed partitions, or slot.mode="
                        "capacity left on)")
                if sh.get("perColumnFallbacks", 0):
                    problems.append(
                        f"{a.session_id} query {q.query_id}: "
                        f"{sh['perColumnFallbacks']} exchange(s) fell "
                        "back to per-column collectives — an unpackable "
                        "column or packed.enabled=false defeats the "
                        "fused shuffle wire format")
                if sh.get("encodableDecodedExchanges", 0):
                    problems.append(
                        f"{a.session_id} query {q.query_id}: "
                        f"{sh['encodableDecodedExchanges']} exchange(s) "
                        "carried dictionary-code columns but shipped "
                        "them DECODED (wide) — enable spark.rapids.tpu"
                        ".encoding.wire.enabled to crush the free "
                        "bytes (docs/performance.md \"Encoded "
                        "execution\")")
                if sh.get("wireDictFallbacks", 0):
                    problems.append(
                        f"{a.session_id} query {q.query_id}: "
                        f"{sh['wireDictFallbacks']} wire dictionary-"
                        "delta broadcast(s) failed verification — the "
                        "launch degraded to the wide wire and the "
                        "dictionary rebroadcasts in full next launch")
                if sh.get("slotOverflowRetries", 0):
                    problems.append(
                        f"{a.session_id} query {q.query_id}: "
                        f"{sh['slotOverflowRetries']} speculative slot "
                        "overflow(s) re-ran at full capacity — data "
                        "skew shifted under a warm exchange site")
            sp = q.spans
            if sp and sp.get("events") and \
                    sp.get("wallMs", 0.0) >= _UNATTRIBUTED_MIN_WALL_MS \
                    and sp.get("unattributedFrac", 0.0) > \
                    UNATTRIBUTED_FRAC_LIMIT:
                problems.append(
                    f"{a.session_id} query {q.query_id}: "
                    f"{sp.get('unattributedMs', 0):.0f}ms of "
                    f"{sp.get('wallMs', 0):.0f}ms wall "
                    f"({sp['unattributedFrac']:.0%}) is UNATTRIBUTED "
                    "by the span taxonomy — an instrumentation blind "
                    "spot; whatever runs there is invisible to every "
                    "perf tool (docs/observability.md)")
            fu = q.fusion
            if fu and fu.get("fusibleChains", 0) > \
                    fu.get("fusedStages", 0):
                lost = fu["fusibleChains"] - fu.get("fusedStages", 0)
                problems.append(
                    f"{a.session_id} query {q.query_id}: {lost} fusible "
                    "operator chain(s) ran UNFUSED — each pays one jit "
                    "dispatch + device materialization per operator per "
                    "batch; check spark.rapids.tpu.fusion.enabled (or "
                    "an unfusible chain member forced the fallback)")
            if fu and fu.get("wireUnfusedLaunches", 0):
                problems.append(
                    f"{a.session_id} query {q.query_id}: "
                    f"{fu['wireUnfusedLaunches']} warm distributed "
                    "stage(s) ran the two-dispatch wire path (compute "
                    "launch + separate pack launch per shard) — "
                    "spark.rapids.tpu.fusion.wire.enabled would fold "
                    "the wire packer into the compute program, one "
                    "launch per shard")
            pl = q.planner
            if pl and pl.get("mispredicts", 0):
                # the SAME factor finish_query counted with — a tuned
                # threshold must not desynchronize the report
                from spark_rapids_tpu.plan.costmodel import \
                    MISPREDICT_FACTOR
                bad = [d for d in pl.get("decisions", [])
                       if d.get("observed") is not None
                       and d.get("predicted")
                       and d["observed"] >=
                       MISPREDICT_FACTOR * d["predicted"]]
                knobs = sorted({d.get("knob", "?") for d in bad}) or \
                    ["?"]
                problems.append(
                    f"{a.session_id} query {q.query_id}: cost model "
                    f"MISPREDICTED {pl['mispredicts']} decision(s) "
                    f"({', '.join(knobs)}) — observed cost >= 4x the "
                    "prediction; the evidence folds back, but repeated "
                    "mispredicts on the same site mean the workload "
                    "shifts faster than the EMA converges "
                    "(docs/performance.md \"Self-tuning planner\")")
            for cmev in q.costmodel:
                problems.append(
                    f"{a.session_id} query {q.query_id}: cost-model "
                    "evidence degraded to built-in defaults "
                    f"({cmev.get('reason', '?')}) — decisions still "
                    "made, never a failed query; check the "
                    "costModel.dir store's health")
            if q.jitcache:
                reasons = sorted({j.get("reason", "?").split(":")[0]
                                  for j in q.jitcache})
                problems.append(
                    f"{a.session_id} query {q.query_id}: "
                    f"{len(q.jitcache)} persistent jit-cache entr"
                    f"{'y' if len(q.jitcache) == 1 else 'ies'} dropped "
                    f"({', '.join(reasons)}) — recompiled fresh (never "
                    "wrong results); check jitCache.dir storage health")
            spilled = sum(q.spill.values()) if q.spill else 0
            if spilled:
                problems.append(
                    f"{a.session_id} query {q.query_id}: spilled "
                    f"{spilled} bytes")
            retries = q.retry.get("retryCount", 0) if q.retry else 0
            splits = q.retry.get("splitAndRetryCount", 0) if q.retry else 0
            if retries or splits:
                problems.append(
                    f"{a.session_id} query {q.query_id}: device OOM "
                    f"recovered — {retries} retries, {splits} splits")
            for r in q.recovery:
                problems.append(
                    f"{a.session_id} query {q.query_id}: recovery "
                    f"action {r.get('action')} after "
                    f"{r.get('fault')} fault")
            problems.extend(_watchdog_problems(
                f"{a.session_id} query {q.query_id}", q.watchdog))
            problems.extend(_corruption_problems(
                f"{a.session_id} query {q.query_id}", q.corruption))
            problems.extend(_checkpoint_problems(
                f"{a.session_id} query {q.query_id}", q.checkpoint,
                recovered=bool(q.recovery)))
            if q.fatal:
                acts = [r.get("action") for r in
                        q.fatal.get("recovery", [])]
                problems.append(
                    f"{a.session_id} query {q.query_id}: fatal after "
                    f"ladder [{', '.join(a for a in acts if a)}] — "
                    f"{q.fatal.get('error', '?')}")
            for b in q.budget:
                problems.append(
                    f"{a.session_id} query {q.query_id}: "
                    f"{b.get('budget')} budget "
                    f"{'exhausted — rejected' if b.get('action') == 'reject' else 'pressure — self-spilled'} "
                    f"({b.get('used')} > {b.get('limit')})")
            adm = q.admission
            if adm and q.duration_ms and \
                    adm.get("waitMs", 0.0) > max(
                        5 * q.duration_ms, 1000.0):
                problems.append(
                    f"{a.session_id} query {q.query_id}: admission "
                    f"starvation — waited {adm['waitMs']:.0f}ms to run "
                    f"{q.duration_ms:.0f}ms; raise serving."
                    "concurrentQueries or spread the tenant load")
        # persistent-cache thrash: a REPEAT of the same plan (matched by
        # normalized logical plan, the compare_apps discipline) that
        # still compiled fresh with zero warm hits — the tier is
        # configured but buying nothing (wrong dir, version churn, or
        # every entry failing verification)
        import re as _re
        seen_plans: Dict[str, int] = {}
        for q in a.queries:
            fu = q.fusion
            if not fu or not fu.get("persistentEnabled"):
                continue
            key = _re.sub(r"\d+", "N", q.logical_plan.strip())
            if not key:
                continue
            if key in seen_plans and fu.get("persistentMisses", 0) > 0 \
                    and fu.get("persistentHits", 0) == 0:
                problems.append(
                    f"{a.session_id} query {q.query_id}: persistent jit "
                    "cache 0% hit on a REPEAT of query "
                    f"{seen_plans[key]} ({fu['persistentMisses']} "
                    "misses, 0 hits) — warm start bought nothing; "
                    "check jitCache.dir persistence and jax/jaxlib "
                    "version churn")
            seen_plans.setdefault(key, q.query_id)
        # result-cache thrash: the cache is ON and the SAME normalized
        # plan repeated, yet no repeat ever hit — every entry is being
        # invalidated (inputs that move every query) or the results
        # never fit maxBytes; the store is configured but buying
        # nothing
        rc_on = str(a.conf.get(
            "spark.rapids.tpu.serving.resultCache.enabled",
            "")).lower() in ("1", "true", "yes", "on")
        if rc_on:
            plan_counts: Dict[str, int] = {}
            for q in a.queries:
                key = _re.sub(r"\d+", "N", q.logical_plan.strip())
                if key:
                    plan_counts[key] = plan_counts.get(key, 0) + 1
            repeats = sum(n - 1 for n in plan_counts.values() if n > 1)
            hit_any = any(
                q.sharing.get("resultCacheHit") for q in a.queries
            ) or any(e.get("kind") == "hit" and
                     e.get("store") == "result"
                     for q in a.queries for e in q.sharing_events)
            if repeats and not hit_any:
                problems.append(
                    f"{a.session_id}: result cache 0% hit over "
                    f"{repeats} repeat(s) of the same plan shape — "
                    "the store is on but buying nothing (inputs "
                    "mutating every query, results over "
                    "resultCache.maxBytes, or uncacheable "
                    "UDF/pandas plans)")
        # template tier that bought nothing: the SAME template
        # fingerprint repeated after warmup, yet repeats still
        # re-traced (jit misses) or nothing was hoistable at all —
        # the refusal list (plan/template.py hoisting rules) says
        # which literals stayed inline and why
        by_tpl: Dict[str, list] = {}
        for q in a.queries:
            t = (q.sharing or {}).get("template")
            if t and t.get("fingerprint"):
                by_tpl.setdefault(t["fingerprint"], []).append(q)
        for fp, qs in by_tpl.items():
            if len(qs) < 2:
                continue
            refusals = sorted({r for q in qs for r in
                               (q.sharing["template"]
                                .get("refusals") or [])})
            why = (f"refused literals: {', '.join(refusals)}"
                   if refusals else "no literals in the plan")
            retraced = [q for q in qs[1:]
                        if q.pipeline.get("jitCacheMisses", 0) > 0]
            if all(q.sharing["template"].get("params", 0) == 0
                   for q in qs):
                problems.append(
                    f"{a.session_id}: template {fp} repeated "
                    f"{len(qs)}x but nothing was hoisted — template "
                    f"tier bought nothing ({why})")
            elif retraced:
                problems.append(
                    f"{a.session_id}: template {fp} re-traced on "
                    f"{len(retraced)} repeat(s) after warmup "
                    f"(query {retraced[0].query_id}) — template tier "
                    f"bought nothing ({why})")
        # interleaver starvation: a query spent far longer blocked at
        # the timeslice gate than doing its own work — co-tenant
        # quanta are too coarse for this mix
        for q in a.queries:
            il = q.sharing.get("interleave") if q.sharing else None
            # gate waits happen INSIDE the query wall (waitMs <=
            # durationMs), so starvation compares the wait to the
            # query's OWN work: duration minus the wait itself
            if il and q.duration_ms and il.get("waitMs", 0.0) > max(
                    5 * (q.duration_ms - il.get("waitMs", 0.0)),
                    1000.0):
                problems.append(
                    f"{a.session_id} query {q.query_id}: interleaver "
                    f"starvation — {il['waitMs']:.0f}ms at the "
                    f"timeslice gate of a {q.duration_ms:.0f}ms "
                    "query; lower co-tenant quanta "
                    "(serving.interleave.quantumBatches) or raise "
                    "this query's weight")
        for j in a.jitcache:
            problems.append(
                f"{a.session_id}: persistent jit-cache entry dropped "
                f"without query attribution ({j.get('reason', '?')})")
        for cmev in a.costmodel:
            problems.append(
                f"{a.session_id}: cost-model evidence degraded to "
                f"built-in defaults ({cmev.get('reason', '?')}) — "
                "decisions still made, never a failed query")
        for r in a.rejections:
            problems.append(
                f"{a.session_id}: query rejected at admission "
                f"({r.get('reason')}) — the session was saturated; "
                "the rejection is the isolation working, but clients "
                "saw a typed AdmissionFault")
        for b in a.budget:
            problems.append(
                f"{a.session_id}: {b.get('budget')} budget event "
                f"without query attribution (action={b.get('action')})")
        if a.max_concurrent() > 1 and (a.recovery or a.watchdog or
                                       a.corruption):
            kinds = [k for k, evs in (("recovery", a.recovery),
                                      ("watchdog", a.watchdog),
                                      ("corruption", a.corruption))
                     if evs]
            problems.append(
                f"{a.session_id}: {'/'.join(kinds)} events without "
                "query attribution while queries ran concurrently — "
                "possible cross-query interference; every robustness "
                "event should carry the owning query's id "
                "(serving/context.py)")
        for r in a.recovery:
            problems.append(
                f"{a.session_id}: recovery action {r.get('action')} "
                f"after {r.get('fault')} fault")
        problems.extend(_watchdog_problems(a.session_id, a.watchdog))
        problems.extend(_corruption_problems(a.session_id,
                                             a.corruption))
        problems.extend(_checkpoint_problems(
            a.session_id, a.checkpoint, recovered=bool(a.recovery)))
        problems.extend(_incremental_problems(
            a.session_id,
            list(a.incremental) + [e for q in a.queries
                                   for e in q.incremental]))
        problems.extend(_fleet_problems(a))
        for f in a.fatal:
            problems.append(
                f"{a.session_id}: fatal query (no attributed id) — "
                f"{f.get('error', '?')}")
    return problems


def _checkpoint_problems(who: str, events: List[dict],
                         recovered: bool = False) -> List[str]:
    """Stage-checkpoint health: eviction thrash (the lineage budget
    cannot hold one stage, so resumes always fall back to full
    re-runs), recoveries that paid the write cost but resumed nothing
    (<1 stage saved across the whole ladder), and payloads that
    failed verification (dropped + subtree re-run — informative, the
    data was never wrong)."""
    out = []
    writes = sum(1 for c in events if c.get("kind") == "write")
    evicts = sum(1 for c in events if c.get("kind") == "evict")
    resumes = sum(1 for c in events if c.get("kind") == "resume")
    crc = [c for c in events if c.get("kind") == "invalid"
           and str(c.get("reason", "")).startswith("crc")]
    if writes and evicts >= writes:
        out.append(
            f"{who}: checkpoint eviction thrash — {evicts} evictions "
            f"over {writes} writes; recovery.checkpoint.maxBytes "
            "cannot hold one stage, so resumes degrade to full "
            "re-runs")
    if recovered and writes and not resumes:
        out.append(
            f"{who}: recovery re-drove the query but resumed <1 "
            f"stage from {writes} written checkpoint(s) — the write "
            "cost bought nothing (evicted/invalidated lineage, or "
            "the fault landed in the first stage)")
    if crc:
        out.append(
            f"{who}: {len(crc)} checkpoint payload(s) failed "
            "verification — dropped and re-run from source (never "
            "wrong bytes); check spill storage health")
    return out


def _incremental_problems(who: str, events: List[dict]) -> List[str]:
    """Continuous-ingest health: ticks that reused zero state after
    the first epoch (the standing query pays full-recompute latency —
    the whole point of incremental state bought nothing), a high
    rollback rate (faults keep killing ticks mid-flight), and
    state-eviction thrash (maxStateBytes cannot hold one epoch, so
    every tick recomputes)."""
    out = []
    commits = [e for e in events if e.get("kind") == "commit"]
    rollbacks = sum(1 for e in events if e.get("kind") == "rollback")
    evicts = sum(1 for e in events if e.get("kind") == "evict")
    cold = [e for e in commits
            if e.get("epoch", 1) > 1 and e.get("mode") == "full"
            and not e.get("reusedState")]
    if cold:
        out.append(
            f"{who}: {len(cold)} ingest tick(s) after the first epoch "
            "reused ZERO standing state (full recompute) — evicted/"
            "invalidated state or a fingerprint that moves every tick; "
            "incremental.maxStateBytes and input stability are the "
            "knobs")
    if commits and rollbacks > max(1, len(commits) // 2):
        out.append(
            f"{who}: {rollbacks} epoch rollback(s) over {len(commits)} "
            "commit(s) — mid-tick faults keep discarding provisional "
            "state; the ingest answers correctly but pays "
            "rollback + full-recompute latency every time")
    if commits and evicts >= len(commits):
        out.append(
            f"{who}: incremental state eviction thrash — {evicts} "
            f"evictions over {len(commits)} commit(s); "
            "incremental.maxStateBytes cannot hold one epoch, so "
            "every tick degrades to full recompute")
    # watermark-stalled state growth: a windowed standing query whose
    # event-time watermark stopped advancing while its state keeps
    # growing — eviction can no longer bound the state (stale event
    # times in the ingest, a delay larger than the data horizon, or a
    # stuck source clock), so "bounded under infinite ingest" is off.
    # Grouped per standing query (the event's `store` id): pooling
    # would let one ADVANCING query's watermarks mask a stalled
    # co-tenant's forever
    by_store: Dict[object, list] = {}
    for e in events:
        if e.get("kind") == "watermark" and \
                e.get("watermark") is not None:
            by_store.setdefault(e.get("store"), []).append(e)
    for store, wms in sorted(by_store.items(),
                             key=lambda kv: str(kv[0])):
        # judge the TAIL of the trail, not its whole history: a query
        # that advanced normally and then stalled (the realistic
        # pattern — source clock sticks mid-life) must still flag;
        # full-trail constancy would be masked by any early advance
        wms = wms[-5:]
        if len(wms) < 3 or len({e["watermark"] for e in wms}) != 1:
            continue
        sizes = [e.get("stateBytes", 0) for e in wms]
        if sizes[-1] > sizes[0] and \
                all(b >= a for a, b in zip(sizes, sizes[1:])):
            out.append(
                f"{who}: watermark-stalled state growth (standing "
                f"query {store}) — the event-time watermark sat at "
                f"{wms[0]['watermark']} across {len(wms)} commits "
                f"while state grew {sizes[0]} -> {sizes[-1]} bytes; "
                "eviction is not bounding this standing query (check "
                "ingest event times vs "
                "incremental.watermarkDelayMs)")
    # exactly-once violation: the same standing query committing a
    # NEW (non-replayed) sink record under one epoch twice means a
    # downstream sink saw an answer twice — the invariant the sink
    # log exists to hold.  Replays are the sanctioned path and are
    # excluded.
    sink_seen: Dict[object, set] = {}
    for e in events:
        if e.get("kind") == "sink" and not e.get("replayed"):
            seen = sink_seen.setdefault(e.get("store"), set())
            ep = e.get("epoch")
            if ep in seen:
                out.append(
                    f"{who}: duplicate sink emission (standing query "
                    f"{e.get('store')}, epoch {ep}) — a downstream "
                    "sink saw one committed answer twice; the "
                    "exactly-once contract is broken")
            seen.add(ep)
    # fleet fan-out that stopped sharing: every round pulling the
    # source once PER SUBSCRIBER means the shared-ingest loan is
    # never usable (schema drift, metadata columns, subscriber
    # backlogs) and the fleet pays lone-runner cost
    rounds = [e for e in events if e.get("kind") == "round"
              and e.get("subscribers", 0) > 1
              and e.get("deltaFiles", 0) > 0]
    if rounds:
        unshared = [e for e in rounds
                    if e.get("sourcePulls", 0) >
                    e.get("deltaFiles", 0)]
        if len(unshared) == len(rounds):
            out.append(
                f"{who}: every fleet round ({len(rounds)}) pulled the "
                "source once per subscriber — the shared-ingest loan "
                "was never usable (mismatched fact scans, metadata "
                "columns, or subscriber catch-up backlogs); the fleet "
                "is paying N-lone-runner ingest cost")
    return out


def _watchdog_problems(who: str, events: List[dict]) -> List[str]:
    """Hang-detection lines: per-point trips with deadline margin, and
    delivered cancellations."""
    out = []
    for w in events:
        point = w.get("point", "?")
        if w.get("kind") == "trip":
            out.append(
                f"{who}: hang detected at {point} — exceeded its "
                f"{w.get('deadlineMs', 0):.0f}ms deadline by "
                f"{w.get('overrunMs', 0):.0f}ms")
        else:
            out.append(
                f"{who}: watchdog cancellation delivered for {point} "
                f"({w.get('elapsedMs', 0):.0f}ms elapsed) — query "
                "re-driven by the recovery ladder")
    return out


def _corruption_problems(who: str, events: List[dict]) -> List[str]:
    out = []
    if events:
        tiers = sorted({c.get("tier", "?") for c in events})
        out.append(
            f"{who}: {len(events)} spill payload(s) failed checksum "
            f"verification (tier {', '.join(tiers)}) — batches "
            "dropped and re-run from source; check spill storage "
            "health")
    return out


def plan_dot(q: QueryInfo) -> str:
    """Physical plan as a DOT digraph (GenerateDot.scala analog)."""
    lines = q.physical_plan.splitlines()
    out = ["digraph plan {", "  rankdir=BT;",
           '  node [shape=box, fontname="monospace"];']
    # indentation encodes the tree
    stack: List[Tuple[int, int]] = []  # (depth, node_id)
    for i, raw in enumerate(lines):
        depth = (len(raw) - len(raw.lstrip())) // 2
        label = raw.strip().replace('"', r'\"')
        out.append(f'  n{i} [label="{label}"];')
        while stack and stack[-1][0] >= depth:
            stack.pop()
        if stack:
            out.append(f"  n{i} -> n{stack[-1][1]};")
        stack.append((depth, i))
    out.append("}")
    return "\n".join(out)


# phase stripe palette for span-traced query bars (fixed order so
# every bar reads the same left-to-right: compile, exchange, compute,
# spill, wait, then the unattributed remainder in grey)
_PHASE_COLORS = (("compile", "#e9c46a"), ("exchange", "#2a9d8f"),
                 ("compute", "#4c956c"), ("spill", "#d1495b"),
                 ("wait", "#b8b8ff"))
_UNATTRIBUTED_COLOR = "#cccccc"


def _phase_stripes(q: QueryInfo, x0: float, y: int, w: float,
                   h: int) -> List[str]:
    """Per-query phase stripes from the span rollup: each phase's
    exclusive time becomes a proportional segment of the query bar.
    Returns [] when the query has no span rollup (pre-span logs fall
    back to the solid status bar)."""
    sp = q.spans
    phases = (sp or {}).get("phases") or {}
    wall = (sp or {}).get("wallMs", 0.0)
    if not phases or wall <= 0 or not q.succeeded:
        return []  # failed/pre-span queries keep the solid status bar
    out = []
    x = x0
    segs = [(name, phases.get(name, 0.0)) for name, _ in _PHASE_COLORS
            if phases.get(name, 0.0) > 0]
    covered = sum(ms for _, ms in segs)
    segs.append(("unattributed", max(wall - covered, 0.0)))
    # worker-thread spans overlap the driver's wall, so summed phase
    # time can exceed it: normalize by the larger of the two so the
    # stripes always fill exactly the query's bar
    total = max(covered, wall)
    colors = dict(_PHASE_COLORS)
    colors["unattributed"] = _UNATTRIBUTED_COLOR
    for name, ms in segs:
        seg_w = w * min(ms / total, 1.0)
        if seg_w < 0.1:
            continue
        out.append(
            f"<rect x='{x:.1f}' y='{y + 4}' width='{seg_w:.1f}' "
            f"height='{h - 10}' fill='{colors[name]}'>"
            f"<title>q{q.query_id} {name}: {ms:.1f} ms</title></rect>")
        x += seg_w
    return out


def generate_timeline(apps: List[AppInfo]) -> str:
    """SVG timeline: one lane per session, one bar per query, colored by
    status (the GenerateTimeline.scala:494 role — theirs draws tasks per
    executor; a single-controller SPMD engine's unit of work is the
    query).  Queries carrying a span rollup (QueryInfo.spans) render as
    phase stripes — compile / exchange / compute / spill / wait — with
    the unattributed remainder in grey; pre-span logs keep the old
    solid bars."""
    apps = [a for a in apps if a.queries]
    if not apps:
        return "<svg xmlns='http://www.w3.org/2000/svg'/>"
    t0 = min(min((q.start_ts or a.start_ts) for q in a.queries)
             for a in apps)
    t1 = max(a.end_ts for a in apps)
    span = max(t1 - t0, 1e-3)
    width, lane_h, pad, label_w = 900, 26, 6, 180
    h = pad * 2 + lane_h * len(apps) + 30
    scale = (width - label_w - pad * 2) / span

    def x(ts):
        return label_w + pad + (ts - t0) * scale

    colors = {"success": "#4c956c", "incomplete": "#b8b8ff"}
    out = [f"<svg xmlns='http://www.w3.org/2000/svg' width='{width}' "
           f"height='{h}' font-family='monospace' font-size='11'>"]
    for i, a in enumerate(apps):
        y = pad + i * lane_h
        out.append(f"<text x='{pad}' y='{y + lane_h - 10}'>"
                   f"{a.session_id[:24]}</text>")
        out.append(f"<line x1='{label_w}' y1='{y + lane_h - 4}' "
                   f"x2='{width - pad}' y2='{y + lane_h - 4}' "
                   f"stroke='#ddd'/>")
        for q in a.queries:
            qs = q.start_ts or a.start_ts
            qe = q.end_ts or (qs + q.duration_ms / 1e3)
            w = max((qe - qs) * scale, 2.0)
            stripes = _phase_stripes(q, x(qs), y, w, lane_h)
            if stripes:
                out.extend(stripes)
                continue
            color = colors.get(q.status, "#d1495b")
            out.append(
                f"<rect x='{x(qs):.1f}' y='{y + 4}' width='{w:.1f}' "
                f"height='{lane_h - 10}' fill='{color}'>"
                f"<title>q{q.query_id}: {q.duration_ms:.1f} ms "
                f"[{q.status}]</title></rect>")
    axis_y = pad + len(apps) * lane_h + 14
    out.append(f"<text x='{label_w}' y='{axis_y}'>0s</text>")
    out.append(f"<text x='{width - 60}' y='{axis_y}'>{span:.1f}s</text>")
    out.append("</svg>")
    return "\n".join(out)


def compare_apps(apps: List[AppInfo]) -> str:
    """Side-by-side session comparison (CompareApplications.scala role):
    per-app totals, then per-query durations matched across apps by
    query id, flagging big regressions."""
    out = ["-- Application comparison --",
           f"{'session':28s} {'queries':>8s} {'total_ms':>10s} "
           f"{'spill_B':>10s} {'fallbacks':>9s}"]
    for a in apps:
        spilled = sum(sum(q.spill.values()) for q in a.queries if q.spill)
        fb = sum(len(q.fallback_ops()) for q in a.queries)
        out.append(f"{a.session_id[:28]:28s} {len(a.queries):8d} "
                   f"{a.total_duration_ms:10.1f} {spilled:10d} {fb:9d}")
    # query ids are engine-global counters, so cross-session identity is
    # the LOGICAL PLAN text (the role SQL ids play in
    # CompareApplications.scala)
    def plans(a):
        import re
        seen = {}
        for q in a.queries:
            # normalize data-dependent literals (row counts in relation
            # describe strings) so the same query over different data
            # sizes still matches
            key = re.sub(r"\d+", "N", q.logical_plan.strip())
            if key and key not in seen:
                seen[key] = q
        return seen
    per_app = [plans(a) for a in apps]
    keys = sorted(set.intersection(*[set(p) for p in per_app])) \
        if len(apps) >= 2 else []
    if keys:
        out.append("\n-- Matched queries (by logical plan) --")
        header = f"{'plan':34s}" + "".join(
            f" {a.session_id[:14]:>16s}" for a in apps)
        out.append(header + f" {'max/min':>8s}")
        for key in keys:
            durs = [p[key].duration_ms for p in per_app]
            ratio = (max(durs) / min(durs)) if min(durs) else 0.0
            flag = "  <-- regression" if ratio >= 2.0 else ""
            label = key.splitlines()[0][:34]
            out.append(f"{label:34s}" + "".join(
                f" {d:16.1f}" for d in durs) + f" {ratio:8.2f}{flag}")
    return "\n".join(out)


def format_report(apps: List[AppInfo], top: int) -> str:
    out = ["=" * 72, "TPU Profiling Report", "=" * 72]
    out.append(f"\nSessions: {len(apps)}, queries: "
               f"{sum(len(a.queries) for a in apps)}")
    out.append("\n-- Operator aggregate (by total opTime) --")
    out.append(f"{'operator':40s} {'time_ms':>10s} {'rows':>12s} "
               f"{'uses':>6s}")
    for name, ms, rows, n in aggregate_ops(apps)[:top]:
        out.append(f"{name:40s} {ms:10.2f} {rows:12d} {n:6d}")
    out.append("\n-- Slowest queries --")
    for sid, q in slowest_queries(apps, top):
        out.append(f"  {sid} q{q.query_id}: {q.duration_ms:.1f} ms "
                   f"[{q.status}]")
    sk = skew_stats(apps)
    if sk:
        out.append("\n-- Duration distribution --")
        out.append(f"  n={sk['queries']} mean={sk['mean_ms']:.1f}ms "
                   f"p50={sk['p50_ms']:.1f}ms max={sk['max_ms']:.1f}ms "
                   f"skew={sk['skew_ratio']:.2f}x")
    pl = pipeline_stats(apps)
    if pl:
        out.append("\n-- Async pipeline --")
        out.append(
            f"  pipelined queries={pl['queries']} "
            f"batches={pl['batches']} "
            f"fill={pl['fill_ratio']:.2f} "
            f"hostSyncs={pl['host_sync_count']} "
            f"uploadOverlap={pl['upload_overlap_ms']:.1f}ms")
        total = pl["jit_cache_hits"] + pl["jit_cache_misses"]
        if total:
            out.append(
                f"  jit cache: {pl['jit_cache_hits']}/{total} hits "
                f"({pl['jit_cache_hits'] / total:.0%})")
    sw = shuffle_wire_stats(apps)
    if sw:
        out.append("\n-- Shuffle wire --")
        out.append(
            f"  distributed queries={sw['queries']} "
            f"exchanges={sw['exchanges']} "
            f"collectives={sw['collectives']} "
            f"bytes={sw['bytes_moved']} "
            f"padding={sw['padding_ratio']:.2f}x "
            f"overflowRetries={sw['slot_overflow_retries']} "
            f"perColumnFallbacks={sw['per_column_fallbacks']}")
        if sw.get("async_exchanges") or sw.get("host_staged_bytes") \
                or sw.get("ragged_exchanges"):
            out.append(
                f"  exchange overlap={sw['exchange_overlap_ms']:.1f}ms"
                f"/{sw['exchange_wall_ms']:.1f}ms "
                f"({sw['overlap_fraction']:.0%}) "
                f"async={sw['async_exchanges']} "
                f"ragged={sw['ragged_exchanges']} "
                f"hostStaged={sw['host_staged_bytes']}B")
        if sw.get("encoded_bytes_saved") or \
                sw.get("encodable_decoded_exchanges"):
            total = sw["bytes_moved"] + sw["encoded_bytes_saved"]
            out.append(
                f"  encoded wire: saved={sw['encoded_bytes_saved']}B "
                f"({sw['encoded_bytes_saved'] / max(total, 1):.0%} of "
                f"decoded) dictDelta={sw['wire_dict_bytes']}B "
                f"dictFallbacks={sw['wire_dict_fallbacks']} "
                f"shippedDecoded={sw['encodable_decoded_exchanges']}")
    fu = fusion_stats(apps)
    if fu:
        out.append("\n-- Whole-stage fusion & compile cache --")
        out.append(
            f"  fusedStages={fu['fused_stages']} "
            f"fusedOperators={fu['fused_operators']} "
            f"dispatchesSaved={fu['dispatches_saved']} "
            f"fusibleChains={fu['fusible_chains']} "
            f"encodedStages={fu['encoded_stages']}")
        ptotal = fu["persistent_hits"] + fu["persistent_misses"]
        if ptotal or fu["persistent_stores"]:
            out.append(
                f"  persistent jit cache: {fu['persistent_hits']}/"
                f"{ptotal} warm hits, stores={fu['persistent_stores']} "
                f"invalid={fu['persistent_invalid']}")
    ss = span_stats(apps)
    if ss:
        out.append("\n-- Where the time went (span tracing) --")
        out.append(
            f"  traced queries={ss['queries']} "
            f"wall={ss['wall_ms']:.1f}ms "
            f"attributed={ss['exclusive_ms']:.1f}ms "
            f"unattributed={ss['unattributed_ms']:.1f}ms "
            f"({ss['unattributed_frac']:.0%}) "
            f"asyncOverlap={ss['overlap_ms']:.1f}ms")
        if ss["phases"]:
            out.append("  phases: " + "  ".join(
                f"{k}={v:.1f}ms" for k, v in ss["phases"].items()))
        for pt, ms in ss["top_points"][:8]:
            out.append(f"    {pt:36s} {ms:10.2f} ms")
    cc = concurrency_stats(apps)
    if cc:
        out.append("\n-- Concurrency & admission --")
        out.append(
            f"  maxConcurrent={cc['max_concurrent']} "
            f"admitted={cc['admitted']} rejected={cc['rejected']} "
            f"waitTotal={cc['total_wait_ms']:.1f}ms "
            f"p50={cc['p50_wait_ms']:.1f}ms "
            f"p95={cc['p95_wait_ms']:.1f}ms "
            f"budgetEvents={cc['budget_events']}")
    cp = checkpoint_stats(apps)
    if cp:
        out.append("\n-- Stage checkpoints --")
        out.append(
            f"  writes={cp['writes']} "
            f"bytes={cp['bytes_written']} "
            f"resumes={cp['resumes']} "
            f"stagesSkipped={cp['stages_skipped']} "
            f"evictions={cp['evictions']} "
            f"invalidations={cp['invalidations']}")
    sh = sharing_stats(apps)
    if sh:
        out.append("\n-- Cross-query reuse --")
        out.append(
            f"  resultCache: hits={sh['result_cache_hits']} "
            f"misses={sh['result_cache_misses']} "
            f"stores={sh['result_cache_stores']} "
            f"invalidations={sh['invalidations']} "
            f"evictions={sh['evictions']}")
        if sh["template_cache_hits"] or sh["template_cache_misses"] \
                or sh["template_cache_stores"]:
            out.append(
                f"  templateCache: hits={sh['template_cache_hits']} "
                f"misses={sh['template_cache_misses']} "
                f"stores={sh['template_cache_stores']}")
        out.append(
            f"  sharedStages: writes={sh['stage_writes']} "
            f"splices={sh['stage_splices']}")
        if sh["interleaved_queries"]:
            out.append(
                f"  interleaver: queries={sh['interleaved_queries']} "
                f"timeslices={sh['timeslices']:.0f} "
                f"wait={sh['interleave_wait_ms']:.1f}ms")
    pdec = planner_stats(apps)
    if pdec:
        out.append("\n-- Planner decisions (cost model) --")
        out.append(
            f"  queries={pdec['queries']} "
            f"decisions={pdec['decisions']} "
            f"evidence={pdec['evidence_decisions']} "
            f"overrides={pdec['override_decisions']} "
            f"replans={pdec['replans']} "
            f"mispredicts={pdec['mispredicts']} "
            f"degradedLoads={pdec['invalid_loads']}")
        if pdec["by_knob"]:
            out.append("  knobs: " + "  ".join(
                f"{k}={v}" for k, v in pdec["by_knob"].items()))
        if pdec["exchange_modes"]:
            out.append("  exchange modes: " + "  ".join(
                f"{k}={v}" for k, v in pdec["exchange_modes"].items()))
    ic = incremental_stats(apps)
    if ic:
        out.append("\n-- Continuous ingest --")
        out.append(
            f"  epochs={ic['commits']} "
            f"incremental={ic['incremental_ticks']} "
            f"fullRecomputes={ic['full_recomputes']} "
            f"reuse={ic['reuse_ratio']:.2f} "
            f"rollbacks={ic['rollbacks']} "
            f"stateEvictions={ic['state_evictions']} "
            f"spliceResumes={ic['splice_resumes']} "
            f"stateBytes={ic['state_bytes']}")
        if ic.get("watermark") is not None:
            out.append(
                f"  watermark={ic['watermark']} "
                f"evictedBuckets={ic['watermark_evicted_buckets']} "
                f"evictedBytes={ic['watermark_evicted_bytes']}")
        if ic.get("sink_commits") or ic.get("sink_replays"):
            out.append(
                f"  sinks: commits={ic['sink_commits']} "
                f"replays={ic['sink_replays']}")
        if ic.get("fleet_rounds"):
            out.append(
                f"  fleet: rounds={ic['fleet_rounds']} "
                f"sourcePulls={ic['fleet_source_pulls']} "
                f"splices={ic['fleet_splices']} "
                f"failures={ic['fleet_failures']}")
    fl = fleet_stats(apps)
    if fl:
        out.append("\n-- Fleet membership --")
        out.append(
            f"  hosts={fl['hosts_seen']} joins={fl['joins']} "
            f"losses={fl['losses']} "
            f"meshShrinks={fl['mesh_shrinks']} "
            f"fenceBumps={fl['fence_bumps']} "
            f"fencedPublishes={fl['fenced_publishes']} "
            f"fleetCrossHits={fl['fleet_cross_hits']}")
        if fl.get("suspects") or fl.get("hedges_fired") \
                or fl.get("quarantines") or fl.get("rejoins"):
            out.append("\n-- Fleet health --")
            out.append(
                f"  suspects={fl['suspects']} "
                f"recoveries={fl['suspect_recoveries']} "
                f"quarantines={fl['quarantines']} "
                f"rejoins={fl['rejoins']} "
                f"hedgesFired={fl['hedges_fired']} "
                f"hedgesWon={fl['hedges_won']} "
                f"duplicatesSuppressed={fl['duplicates_suppressed']}")
            # per-host score timeline: each state transition with the
            # score that drove it, in log order — the gray-failure
            # post-mortem trail (when did it go bad, how bad, when did
            # it come back)
            for a in apps:
                line = []
                for ev in a.fleet:
                    k = ev.get("kind")
                    if k in ("suspect", "recovered", "quarantine",
                             "rejoin"):
                        sc = ev.get("score")
                        tag = f"{k}@host{ev.get('host')}"
                        if sc is not None:
                            tag += f"(x{sc})"
                        line.append(tag)
                if line:
                    out.append(
                        f"  {a.session_id}: " + " -> ".join(line))
    problems = health_check(apps)
    out.append("\n-- Health check --")
    if problems:
        out.extend(f"  ! {p}" for p in problems)
    else:
        out.append("  no failures, no spill")
    return "\n".join(out)


def main(argv: List[str] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="spark_rapids_tpu.tools.profiling", description=__doc__)
    ap.add_argument("logdir")
    ap.add_argument("--top", type=int, default=10)
    ap.add_argument("--dot", type=int, default=None, metavar="QUERYID",
                    help="print a DOT graph of this query's physical plan")
    ap.add_argument("--timeline", metavar="FILE.svg", default=None,
                    help="write an SVG timeline of all sessions/queries")
    ap.add_argument("--compare", action="store_true",
                    help="side-by-side comparison of the loaded sessions")
    ap.add_argument("--filter-app", metavar="REGEX", default=None,
                    help="only sessions whose id matches the regex")
    ap.add_argument("--started-after", type=float, default=None,
                    metavar="EPOCH", help="only sessions started at/after "
                    "this epoch-seconds timestamp")
    ap.add_argument("--newest", type=int, default=None, metavar="N",
                    help="only the N most recently started sessions")
    ap.add_argument("--site-history", metavar="OBS_DIR", default=None,
                    help="also print the per-site observation history "
                    "persisted beside the AOT cache dir "
                    "(utils/tracing.ObservationStore)")
    args = ap.parse_args(argv)
    if args.site_history and args.logdir == "-":
        # site history needs no event log: allow '-' as the logdir
        print(site_history(args.site_history))
        return 0
    from spark_rapids_tpu.tools.eventlog import filter_apps
    apps = filter_apps(load_logs(args.logdir), match=args.filter_app,
                       started_after=args.started_after,
                       newest=args.newest)
    if not apps:
        print("no event logs found", file=sys.stderr)
        return 1
    if args.timeline:
        with open(args.timeline, "w", encoding="utf-8") as fh:
            fh.write(generate_timeline(apps))
        print(f"wrote {args.timeline}")
        return 0
    if args.compare:
        print(compare_apps(apps))
        return 0
    if args.dot is not None:
        for a in apps:
            for q in a.queries:
                if q.query_id == args.dot:
                    print(plan_dot(q))
                    return 0
        print(f"query {args.dot} not found", file=sys.stderr)
        return 1
    print(format_report(apps, args.top))
    if args.site_history:
        print()
        print(site_history(args.site_history))
    return 0


if __name__ == "__main__":
    sys.exit(main())
