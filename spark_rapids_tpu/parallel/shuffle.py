"""Shuffle as an SPMD collective: ONE fused packed all-to-all per exchange.

This replaces the reference's entire UCX transport stack (shuffle-plugin/,
RapidsShuffleClient/Server, bounce buffers, heartbeats — SURVEY.md section
2.5): instead of point-to-point pull with metadata requests, every shard
partitions its rows by destination, lays them out contiguously, and a
collective moves all slices across ICI simultaneously.  Peer discovery,
connection management, and retry logic disappear — the collective is
compiled into the XLA program.

Wire format (the fused data path): all fixed-width columns of a batch are
gathered into width-homogeneous lane groups:

* **u32 group** — 4-byte columns and int64 are byte-reinterpreted
  (``jax.lax.bitcast_convert_type``) to one / two uint32 lanes; payload
  shape ``[num_parts, slot, lanes32]``.
* **u8 group** — bool/int8 columns contribute one uint8 lane, int16 two,
  and every validity mask is bit-packed eight-to-a-lane at the tail;
  payload shape ``[num_parts, slot, lanes8]``.
* **f64 group** — float64 columns ride as themselves, one lane each,
  payload shape ``[num_parts, slot, lanes64]``: the TPU X64 rewriter
  refuses every ``bitcast-convert`` whose operand is an f64 (to u32, to
  u8 and to int64 alike), so a double is never reinterpreted on the
  send side.

Each group moves with ONE ``all_to_all`` and the slice→dense compaction
index map is computed once per exchange and shared by every lane — an
exchange costs O(distinct widths) ≤ 3 collectives plus the counts vector,
instead of O(columns + validity masks).  ``packed.enabled=false`` (or an
unpackable column) falls back to the per-column collectives, which still
reuse the shared compaction indices.

Raggedness: all_to_all needs equal-sized slices, so each (src, dst) slice
is padded to ``slot`` rows, with true counts exchanged alongside.  Slot
sizing is the :class:`SlotPlanner`'s job (modes ``adaptive`` / ``fixed`` /
``capacity``): exchange sites feed it their materialized per-destination
histogram max, it answers with a power-of-two slot smoothed by a per-site
EMA (stable slots = stable jit-cache keys), and warm ``adaptive`` sites
may launch *speculatively* — skipping the stats hostsync entirely — with
a slot-overflow check after the launch that re-runs at full capacity and
records a degradable recovery action rather than ever dropping rows.

Every exchange also reports wire observability (collectives launched,
payload bytes, padding ratio, overflow retries) through
:class:`ShuffleWireMetrics` → eventlog ``QueryInfo.shuffle`` →
``tools/profiling`` health checks (docs/performance.md "Shuffle wire
format").
"""

from __future__ import annotations

import threading
from contextlib import contextmanager
from typing import Dict, Hashable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from spark_rapids_tpu.ops.expressions import ColVal
from spark_rapids_tpu.parallel.partitioning import layout_by_partition
from spark_rapids_tpu.robustness.inject import register_point

# chaos surface: bit-flip the compressed dictionary-delta broadcast a
# wire-encoded exchange ships (WireDictBroadcast) — verification failure
# degrades that launch to the wide wire, exact results either way
register_point("shuffle.wire.dict")


@contextmanager
def launch_checkpoint():
    """The single host-side checkpoint per exchange-bearing program
    launch: fires the "shuffle.exchange" injection point exactly once
    (count-based chaos rules see one checkpoint per launch whether the
    traced program was cached or not — packed or per-column alike) and
    runs the host-side launch (trace + dispatch) under a watchdog
    deadline.  XLA dispatch is asynchronous, so a collective that
    wedges DURING execution surfaces at the stage's host sync / the
    whole-query deadline instead — cancellation is cooperative and only
    host-touching checkpoints can deliver it (robustness/watchdog.py)."""
    from spark_rapids_tpu.robustness import watchdog
    from spark_rapids_tpu.robustness.inject import fire
    with watchdog.section("shuffle.exchange",
                          deadline_ms=_launch_deadline_ms()):
        fire("shuffle.exchange")
        yield


def _launch_deadline_ms() -> Optional[float]:
    """Exchange-launch deadline, DCN-aware: a cross-host collective is
    orders of magnitude slower than the same bytes over ICI, so when
    the active session's data axis spans hosts the per-point deadline
    scales by ``spark.rapids.tpu.fleet.dcnDeadlineScale`` — otherwise
    the deadline tuned for ICI misfires on every healthy DCN exchange.
    None defers to the watchdog's own per-point resolution (the
    single-host behavior, unchanged)."""
    try:
        from spark_rapids_tpu.api.session import TpuSession
        session = TpuSession._active
    except ImportError:  # torn-down interpreter only
        return None
    mesh = getattr(session, "mesh", None)
    if session is None or mesh is None:
        return None
    from spark_rapids_tpu.config import rapids_conf as rc
    from spark_rapids_tpu.parallel.mesh import axis_link_kind
    if axis_link_kind(mesh) != "dcn":
        return None
    base = session.conf.watchdog_deadline_ms("shuffle.exchange")
    if base is None or base <= 0:
        return None
    return float(base) * float(
        session.conf.get(rc.FLEET_DCN_DEADLINE_SCALE))


def pick_slot(max_slice: int, capacity: int, floor: int = 8) -> int:
    """Slot size for ``exchange`` from a materialized per-destination
    histogram: the true max slice count bucketed up to a power of two
    (<= 2x the ideal bytes on ICI), capped at the full capacity."""
    s = floor
    while s < max_slice:
        s <<= 1
    return min(s, capacity)


class RaggedPlan:
    """Skew-adaptive slot plan for one stats-sized exchange.

    The base all_to_all is sized from the COLD (src, dst) slices; the
    few hot slices' surplus rows (beyond ``base_slot``) ride
    collective-permutes that transmit only on their own link — wire
    rows stop scaling as ``num_parts * hottest_slice``.  ``pairs`` is
    the static hot set; rounds decompose it into partial permutations
    (each src/dst at most once per round, the ppermute contract).
    Hashable: a plan is part of the consumer's jit-cache signature.
    """

    def __init__(self, num_parts: int, base_slot: int, surplus_slot: int,
                 pairs):
        import numpy as np
        self.num_parts = num_parts
        self.base_slot = int(base_slot)
        self.surplus_slot = int(surplus_slot)
        self.pairs = tuple(sorted(tuple(map(int, p)) for p in pairs))
        # greedy round decomposition into partial permutations
        remaining = list(self.pairs)
        rounds = []
        while remaining:
            used_s, used_d, rnd = set(), set(), []
            for p in list(remaining):
                s, d = p
                if s not in used_s and d not in used_d:
                    rnd.append(p)
                    used_s.add(s)
                    used_d.add(d)
                    remaining.remove(p)
            rounds.append(tuple(rnd))
        self.rounds = tuple(rounds)
        # static lookup tables the SPMD trace indexes by axis_index
        n = num_parts
        self.round_dst_by_src = np.zeros((len(rounds), n), dtype=np.int32)
        self.round_for_src = np.zeros((n, n), dtype=np.int32)
        self.limits = np.full((n, n), self.base_slot, dtype=np.int32)
        pairs_per_dest = np.zeros(n, dtype=np.int64)
        for r, rnd in enumerate(self.rounds):
            for s, d in rnd:
                self.round_dst_by_src[r, s] = d
                self.round_for_src[d, s] = r
                self.limits[s, d] = self.base_slot + self.surplus_slot
                pairs_per_dest[d] += 1
        self.max_pairs_per_dest = int(pairs_per_dest.max()) if n else 0

    @property
    def out_capacity(self) -> int:
        """Static receive capacity every shard allocates: the base
        slices plus the worst destination's surplus buffers."""
        return self.num_parts * self.base_slot + \
            self.max_pairs_per_dest * self.surplus_slot

    def wire_rows(self, nshards: int) -> int:
        """Exact wire rows one launch moves: every shard transmits the
        full base payload; each surplus pair transmits once (a
        collective-permute only moves the named link)."""
        return nshards * self.num_parts * self.base_slot + \
            len(self.pairs) * self.surplus_slot

    def cache_key(self):
        return ("ragged", self.num_parts, self.base_slot,
                self.surplus_slot, self.pairs)

    def __repr__(self):
        return (f"RaggedPlan(base={self.base_slot}, "
                f"surplus={self.surplus_slot}x{len(self.pairs)}, "
                f"rounds={len(self.rounds)})")


def plan_ragged(counts, capacity: int, min_savings: float = 1.5,
                max_pairs: Optional[int] = None) -> Optional[RaggedPlan]:
    """Ragged plan from a materialized [src, dst] histogram, or None
    when the uniform slot wins (no skew, too many hot pairs, or the
    wire-rows saving is below ``min_savings``)."""
    import numpy as np
    counts = np.asarray(counts)
    if counts.ndim != 2 or not counts.size:
        return None
    n_src, n_dst = counts.shape
    max_pairs = max_pairs if max_pairs is not None else 2 * n_dst
    u_slot = pick_slot(int(counts.max()), capacity)
    uniform_rows = n_src * n_dst * u_slot
    best = None
    best_rows = uniform_rows
    base = 8
    while base < u_slot:
        pairs = np.argwhere(counts > base)
        if 0 < len(pairs) <= max_pairs:
            surplus = pick_slot(int((counts - base).max()), capacity)
            rows = n_src * n_dst * base + len(pairs) * surplus
            if rows < best_rows:
                best_rows = rows
                best = (base, surplus, [tuple(p) for p in pairs])
        base <<= 1
    if best is None or uniform_rows / max(best_rows, 1) < min_savings:
        return None
    return RaggedPlan(n_dst, best[0], best[1], best[2])


def ragged_enabled(conf=None) -> Tuple[bool, float]:
    """(enabled, minSavings) for skew-adaptive ragged slot planning."""
    from spark_rapids_tpu.config import rapids_conf as rc
    if conf is None:
        from spark_rapids_tpu.api.session import TpuSession
        s = TpuSession._active
        if s is None:
            return (rc.SHUFFLE_SLOT_RAGGED_ENABLED.default,
                    rc.SHUFFLE_SLOT_RAGGED_FACTOR.default)
        conf = s.conf
    return (conf.get(rc.SHUFFLE_SLOT_RAGGED_ENABLED),
            conf.get(rc.SHUFFLE_SLOT_RAGGED_FACTOR))


def topology_strategy(mesh, conf=None) -> str:
    """Collective strategy for the mesh's exchange axis: the conf knob,
    with 'auto' resolving by link kind (all_to_all on ICI, gather-then-
    redistribute on a DCN-spanning axis) — parallel/mesh.py topology."""
    from spark_rapids_tpu.config import rapids_conf as rc
    if conf is None:
        from spark_rapids_tpu.api.session import TpuSession
        s = TpuSession._active
        conf = s.conf if s is not None else None
    strategy = conf.get(rc.SHUFFLE_TOPOLOGY_STRATEGY) if conf is not None \
        else rc.SHUFFLE_TOPOLOGY_STRATEGY.default
    if strategy != "auto":
        return strategy
    from spark_rapids_tpu.parallel.mesh import axis_link_kind
    return "gather" if axis_link_kind(mesh) == "dcn" else "all_to_all"


def wire_encoding_enabled(conf=None) -> bool:
    """Resolve spark.rapids.tpu.encoding.wire.enabled (the compressed
    device wire for dictionary-code columns); consumers resolve at
    construction and bake the narrowed column set into their jit
    signatures."""
    from spark_rapids_tpu.config import rapids_conf as rc
    if conf is None:
        from spark_rapids_tpu.api.session import TpuSession
        s = TpuSession._active
        if s is None:
            return rc.ENCODING_WIRE_ENABLED.default
        conf = s.conf
    from spark_rapids_tpu.plan.costmodel import model_for_conf
    cm = model_for_conf(conf)
    if cm is not None:
        # self-tuning planner: the model decides when the conf key is
        # unset (an explicitly-set key stays an override inside it);
        # conf-gated so a knobs-off session planning while a model-on
        # session is _active keeps bit-identical HEAD parity
        return cm.wire_encoding()
    return conf.get(rc.ENCODING_WIRE_ENABLED)


def wire_fusion_enabled(conf=None) -> bool:
    """Resolve spark.rapids.tpu.fusion.wire.enabled: explicit conf >
    active session > entry default.  Consumers resolve at construction;
    the fused program's jit key carries its own component (never the
    shared stage signature, so stage ids stay byte-identical fused or
    not)."""
    from spark_rapids_tpu.config import rapids_conf as rc
    if conf is None:
        from spark_rapids_tpu.api.session import TpuSession
        s = TpuSession._active
        if s is None:
            return rc.FUSION_WIRE_ENABLED.default
        conf = s.conf
    return conf.get(rc.FUSION_WIRE_ENABLED)


def packed_enabled(conf=None) -> bool:
    """Resolve spark.rapids.tpu.shuffle.packed.enabled: explicit conf >
    active session > entry default.  Exchange consumers resolve this at
    construction and bake it into their jit-cache signatures, so a conf
    flip can never be masked by a cached trace."""
    from spark_rapids_tpu.config import rapids_conf as rc
    if conf is None:
        from spark_rapids_tpu.api.session import TpuSession
        s = TpuSession._active
        if s is None:
            return rc.SHUFFLE_PACKED_ENABLED.default
        conf = s.conf
    return conf.get(rc.SHUFFLE_PACKED_ENABLED)


# ------------------------------------------------------------- lane packing --

_U32 = "u32"
_U8 = "u8"
_F64 = "f64"
# wire bytes per lane of each group
_LANE_BYTES = {_U32: 4, _U8: 1, _F64: 8}


class _PackPlan:
    """Lane assignment for one exchange's columns: which width group and
    lane range each column occupies, plus the bit position of every
    validity mask in the u8 group's packed-validity tail lanes."""

    def __init__(self, cols: Sequence[ColVal]):
        self.col_group: List[str] = []
        self.col_start: List[int] = []
        self.col_lanes: List[int] = []
        self.col_dtype = [c.values.dtype for c in cols]
        self.valid_bit: List[Optional[int]] = []
        import numpy as np
        n32 = n8 = n64 = nbits = 0
        for c in cols:
            w = np.dtype(c.values.dtype).itemsize
            if c.values.dtype == jnp.float64:
                grp, lanes, n64 = _F64, 1, n64 + 1
                self.col_start.append(n64 - 1)
            elif w in (4, 8):
                grp, lanes, n32 = _U32, w // 4, n32 + w // 4
                self.col_start.append(n32 - w // 4)
            elif w in (1, 2):
                grp, lanes, n8 = _U8, w, n8 + w
                self.col_start.append(n8 - w)
            else:
                raise _Unpackable(f"column width {w} has no lane group")
            self.col_group.append(grp)
            self.col_lanes.append(lanes)
            if c.validity is not None:
                self.valid_bit.append(nbits)
                nbits += 1
            else:
                self.valid_bit.append(None)
        self.n32 = n32
        self.n8_data = n8
        self.n8 = n8 + (nbits + 7) // 8
        self.n64 = n64

    @property
    def lanes(self) -> Dict[str, int]:
        """Lane count of every group this plan ships."""
        return {g: n for g, n in ((_U32, self.n32), (_U8, self.n8),
                                  (_F64, self.n64)) if n}

    @property
    def collectives(self) -> int:
        """Data collectives this plan launches (counts vector excluded)."""
        return len(self.lanes)


class _Unpackable(Exception):
    """A column the lane packer cannot transport (non-fixed-width)."""


# site -> trace-time lane report ({"collectives", "row_bytes",
# "group_row_bytes"}): the EXACT wire cost of the program a
# consumer site compiled, recorded by the exchange body itself (it
# alone sees runtime dtypes/nullability).  Keyed by the consumer's jit
# signature, so it persists across consumer reconstruction exactly as
# long as the compiled program does; metrics fall back to the
# conservative estimate only before first trace.
_WIRE_REPORTS: Dict[Hashable, dict] = {}


def wire_report(site) -> Optional[dict]:
    return _WIRE_REPORTS.get(site)


def _ragged_site(site, rp: "RaggedPlan"):
    """Report key for the RAGGED variant of an exchange site: the same
    consumer site compiles distinct uniform/ragged programs (different
    collectives, same jit-sig prefix), so their trace-time reports must
    not overwrite each other.  Derived identically by the exchange body
    (write) and record_exchange_metrics (read)."""
    return None if site is None else (site, "ragged", rp.cache_key())


def _record_wire_report(site, cols, plan, surplus_rounds: int = 0,
                        fallback: bool = False,
                        saved_per_row: int = 0) -> None:
    import numpy as np
    if site is None:
        return
    nullable = sum(1 for c in cols if c.validity is not None)
    if plan is not None:
        # a ragged plan adds one collective-permute per surplus round
        # per width group on top of the base all_to_alls
        collectives = 1 + plan.collectives * (1 + surplus_rounds)
        group_row_bytes = {g: _LANE_BYTES[g] * n
                           for g, n in plan.lanes.items()}
        row_bytes = sum(group_row_bytes.values())
    else:
        # per-column wire: one collective per column + mask; validity
        # rides as full bool lanes (1 byte/row), not bit-packed
        collectives = 1 + len(cols) + nullable
        row_bytes = sum(
            max(np.dtype(c.values.dtype).itemsize, 1) for c in cols) \
            + nullable
        group_row_bytes = {}
    # saved_per_row: bytes/row the wire-encoding narrow transform shaved
    # BEFORE packing — cols already hold the narrowed dtypes, so
    # row_bytes above is the true (post-encoding) wire cost and this
    # field attributes the delta (encodedBytesSaved)
    _WIRE_REPORTS[site] = {"collectives": collectives,
                           "row_bytes": row_bytes,
                           "group_row_bytes": group_row_bytes,
                           "row_bytes_saved": saved_per_row,
                           "fallback": fallback}


def _narrow_wire_cols(cols: Sequence[ColVal],
                      wire_encode) -> Tuple[List[ColVal], Tuple[int, ...]]:
    """Trace-time wire transform for dictionary-code columns: an int64
    code column ships as ONE i32 lane instead of two (codes are dense
    dictionary ranks, so they fit i32 by construction — the encoders
    bound dictionaries far below 2^31).  Returns the transformed list
    plus the indices actually narrowed (for the inverse widen)."""
    if not wire_encode:
        return list(cols), ()
    out = list(cols)
    narrowed = []
    for i in wire_encode:
        c = out[i]
        if getattr(c.values, "dtype", None) == jnp.int64:
            out[i] = ColVal(c.dtype, c.values.astype(jnp.int32),
                            c.validity, c.offsets)
            narrowed.append(int(i))
    return out, tuple(narrowed)


def _widen_wire_cols(out_cols: List[ColVal],
                     narrowed: Tuple[int, ...]) -> List[ColVal]:
    """Invert :func:`_narrow_wire_cols` on the received columns —
    downstream consumers see the exact int64 code values (dead padding
    rows widen to different-but-dead garbage; validity/in-range masks
    already exclude them)."""
    for i in narrowed:
        c = out_cols[i]
        out_cols[i] = ColVal(c.dtype, c.values.astype(jnp.int64),
                             c.validity, c.offsets)
    return out_cols


def _plan_pack(cols: Sequence[ColVal]) -> Optional[_PackPlan]:
    if not cols:
        return None
    try:
        for c in cols:
            if c.offsets is not None or getattr(c.values, "ndim", 0) != 1:
                raise _Unpackable("offsets / non-vector column")
        return _PackPlan(cols)
    except _Unpackable:
        return None


def _pack_payloads(cols: Sequence[ColVal], plan: _PackPlan, sel=None):
    """Build the lane payloads, ``{group: [..., lanes]}`` for every group
    the plan ships.  ``sel`` is an optional gather index array (the
    padded-slot send layout); lanes inherit its shape with one trailing
    lane axis."""

    def take(a):
        return a if sel is None else a[sel]

    lanes32: List[jnp.ndarray] = [None] * plan.n32
    lanes8: List[jnp.ndarray] = [None] * plan.n8
    lanes64: List[jnp.ndarray] = [None] * plan.n64
    shape = None
    for c, grp, start, nlanes in zip(cols, plan.col_group, plan.col_start,
                                     plan.col_lanes):
        send = take(c.values)
        shape = send.shape
        if grp == _F64:
            lanes64[start] = send
        elif grp == _U32:
            if nlanes == 1:
                lanes32[start] = jax.lax.bitcast_convert_type(
                    send, jnp.uint32)
            else:
                w = jax.lax.bitcast_convert_type(send, jnp.uint32)
                for i in range(nlanes):
                    lanes32[start + i] = w[..., i]
        elif send.dtype == jnp.bool_:
            lanes8[start] = send.astype(jnp.uint8)
        elif nlanes == 1:
            lanes8[start] = jax.lax.bitcast_convert_type(send, jnp.uint8)
        else:
            w = jax.lax.bitcast_convert_type(send, jnp.uint8)
            for i in range(nlanes):
                lanes8[start + i] = w[..., i]
    # validity tail: eight masks per uint8 lane
    for lane in range(plan.n8_data, plan.n8):
        lanes8[lane] = jnp.zeros(shape, dtype=jnp.uint8)
    for c, bit in zip(cols, plan.valid_bit):
        if bit is None:
            continue
        lane = plan.n8_data + bit // 8
        lanes8[lane] = lanes8[lane] | jnp.left_shift(
            take(c.validity).astype(jnp.uint8), jnp.uint8(bit % 8))
    return {g: jnp.stack(lanes, axis=-1)
            for g, lanes in ((_U32, lanes32), (_U8, lanes8),
                             (_F64, lanes64)) if lanes}


def _unpack_payloads(cols: Sequence[ColVal], plan: _PackPlan,
                     flat: Dict[str, jnp.ndarray],
                     in_range) -> List[ColVal]:
    """Invert :func:`_pack_payloads` on already index-compacted lane
    matrices (``flat[group]``: [cap, lanes])."""
    flat32, flat8 = flat.get(_U32), flat.get(_U8)
    out: List[ColVal] = []
    for c, grp, start, nlanes, bit in zip(
            cols, plan.col_group, plan.col_start, plan.col_lanes,
            plan.valid_bit):
        if grp == _F64:
            vals = flat[_F64][:, start]
        elif grp == _U32:
            sub = flat32[:, start:start + nlanes]
            vals = jax.lax.bitcast_convert_type(
                sub[:, 0] if nlanes == 1 else sub, c.values.dtype)
        elif c.values.dtype == jnp.bool_:
            vals = flat8[:, start] != 0
        else:
            sub = flat8[:, start:start + nlanes]
            vals = jax.lax.bitcast_convert_type(
                sub[:, 0] if nlanes == 1 else sub, c.values.dtype)
        validity = None
        if bit is not None:
            bits = jnp.bitwise_and(
                jnp.right_shift(flat8[:, plan.n8_data + bit // 8],
                                jnp.uint8(bit % 8)), jnp.uint8(1))
            validity = jnp.where(in_range, bits != 0, False)
        out.append(ColVal(c.dtype, vals, validity))
    return out


# ---------------------------------------------------------------- exchange --

class WirePayload:
    """The wire-ready send side of one exchange, produced by
    :func:`pack_for_wire` inside the SAME traced program as the compute
    that fed it: partition-sorted columns, narrowed code columns, the
    per-destination counts, and (when the lane packer accepts the
    columns) the per-group lane payloads in the padded-slot send
    layout.  ``exchange`` composes this with the all_to_all and the
    receive-side unpack; a fused distributed stage emits it without any
    intermediate dispatch boundary."""

    __slots__ = ("cols", "narrowed", "counts", "starts", "src",
                 "plan", "payloads")

    def __init__(self, cols, narrowed, counts, starts, src, plan,
                 payloads):
        self.cols = cols
        self.narrowed = narrowed
        self.counts = counts
        self.starts = starts
        self.src = src
        self.plan = plan
        self.payloads = payloads


def pack_for_wire(cols: Sequence[ColVal], pids: jnp.ndarray, nrows,
                  num_parts: int, slot: int,
                  packed: bool = True,
                  wire_encode: Sequence[int] = ()) -> WirePayload:
    """Composable traced lane packer: everything the send side of an
    exchange does before the collective — layout_by_partition, wire
    narrowing, padded-slot gather indices, bitcast lane payloads and
    packed validity tails — as one traceable function.  Callers fuse
    it into the producing program so the stage's compute and its
    wire-ready payload come out of ONE dispatch per shard; ``plan`` is
    None when the columns are unpackable (or ``packed`` is False) and
    the caller ships per-column."""
    capacity = pids.shape[0]
    sorted_cols, counts, starts = layout_by_partition(
        cols, pids, nrows, num_parts)
    sorted_cols, narrowed = _narrow_wire_cols(sorted_cols, wire_encode)
    j = jnp.arange(slot, dtype=jnp.int32)[None, :]
    src = jnp.clip(starts[:, None] + j, 0, capacity - 1)
    plan = _plan_pack(sorted_cols) if packed else None
    payloads = None
    if plan is not None:
        payloads = _pack_payloads(sorted_cols, plan, sel=src)
    return WirePayload(sorted_cols, narrowed, counts, starts, src,
                       plan, payloads)


def _compaction_indices(recv_counts, total, num_parts: int, slot: int):
    """Slice→dense map shared by every lane/column of one exchange:
    for each dense output position, the (source slice, offset) it reads
    and whether it is a live row."""
    recv_starts = jnp.concatenate(
        [jnp.zeros(1, dtype=jnp.int32), jnp.cumsum(recv_counts)[:-1]])
    pos = jnp.arange(num_parts * slot, dtype=jnp.int32)
    part = jnp.searchsorted(recv_starts, pos, side="right") - 1
    part = jnp.clip(part, 0, num_parts - 1)
    offset = jnp.clip(pos - recv_starts[part], 0, slot - 1)
    in_range = pos < total
    return part, offset, in_range


def exchange(cols: Sequence[ColVal], pids: jnp.ndarray, nrows,
             axis_name: str, num_parts: int,
             slot: Optional[int] = None,
             packed: Optional[bool] = None,
             with_overflow: bool = False,
             report_site=None,
             ragged: Optional[RaggedPlan] = None,
             wire_encode: Sequence[int] = ()):
    """All-to-all exchange inside shard_map.

    Every shard sends row r to shard ``pids[r]``.  Returns (received
    cols, received nrows) — plus a per-shard overflow flag (any local
    (src, dst) slice larger than ``slot``, i.e. rows were dropped and
    the launch must be re-run with a bigger slot) when
    ``with_overflow`` is set.  Received capacity is
    ``num_parts * slot``.  Only fixed-width columns (strings must be
    dictionary-encoded upstream).

    ``packed`` selects the fused lane-payload wire format (module
    docstring); None resolves the session conf.  Callers that jit-cache
    programs containing this body must bake the resolved flag into
    their cache signature.

    The "shuffle.exchange" injection point does NOT fire here: this
    body runs at trace time (and not at all on a jit-cache hit), and a
    launch with several exchanges (shuffle join) would multi-fire.
    ``launch_checkpoint`` above is the single host-side checkpoint per
    exchange-bearing program launch — callers invoke it right before
    dispatching the compiled program.
    """
    capacity = pids.shape[0]
    slot = slot or capacity
    if packed is None:
        packed = packed_enabled()
    # the send side — partition layout, wire narrowing (compressed
    # wire narrows caller-marked code columns AFTER partitioning and
    # BEFORE lane packing, so every wire variant ships the narrow form
    # and the trace-time report meters post-encoding bytes), padded-
    # slot gather and lane payloads — is the composable packer; fused
    # stages emit it from the producing program directly
    pay = pack_for_wire(cols, pids, nrows, num_parts, slot,
                        packed=packed, wire_encode=wire_encode)
    sorted_cols, narrowed = pay.cols, pay.narrowed
    counts, starts, src, plan = pay.counts, pay.starts, pay.src, pay.plan
    saved_pr = 4 * len(narrowed)

    # counts for my slices on every peer: all_to_all of the counts vector
    recv_counts = jax.lax.all_to_all(
        counts.reshape(num_parts, 1), axis_name, split_axis=0,
        concat_axis=0).reshape(num_parts)
    if ragged is not None and plan is not None:
        # skew-adaptive ragged wire (needs the lane-packed format; an
        # unpackable column set falls through to the uniform slot the
        # caller also passed — trace-time consistent either way)
        _record_wire_report(_ragged_site(report_site, ragged),
                            sorted_cols, plan,
                            surplus_rounds=len(ragged.rounds),
                            saved_per_row=saved_pr)
        res = _exchange_ragged(sorted_cols, plan, counts, recv_counts,
                               starts, capacity, axis_name, num_parts,
                               ragged, with_overflow)
        if with_overflow:
            rcols, rtotal, rovf = res
            return _widen_wire_cols(rcols, narrowed), rtotal, rovf
        rcols, rtotal = res
        return _widen_wire_cols(rcols, narrowed), rtotal
    if ragged is not None:
        # ragged was requested but the lane packer refused the columns:
        # this program runs the uniform per-column wire at the caller's
        # fallback slot.  Mark the RAGGED report key at trace time so
        # consumer accounting bills the program that actually moved
        # bytes (the plain-site report may belong to a different
        # variant compiled at the same signature).
        _record_wire_report(_ragged_site(report_site, ragged),
                            sorted_cols, None, fallback=True,
                            saved_per_row=saved_pr)

    total = recv_counts.sum()
    # the slice→dense compaction map, computed ONCE and shared by every
    # lane (packed) or column (fallback)
    part, offset, in_range = _compaction_indices(
        recv_counts, total, num_parts, slot)

    _record_wire_report(report_site, sorted_cols, plan,
                        saved_per_row=saved_pr)
    if packed and plan is None and cols:
        # trace-time breadcrumb: the fused wire was requested but these
        # columns are unpackable, so this program runs per-column
        # collectives.  Counted here (not at the consumer, which only
        # knows the conf flag) so perColumnFallbacks — and the
        # profiling health check built on it — reflects the EFFECTIVE
        # wire format.  Trace-time means once per compiled program, not
        # per launch; a nonzero count is the signal, not a launch tally.
        metrics_for_session().record_fallback()
    if plan is not None:
        flat = {g: jax.lax.all_to_all(p, axis_name, split_axis=0,
                                      concat_axis=0)[part, offset]
                for g, p in pay.payloads.items()}
        out_cols = _unpack_payloads(sorted_cols, plan, flat, in_range)
    else:
        out_cols = []
        for c in sorted_cols:
            recv = jax.lax.all_to_all(c.values[src], axis_name,
                                      split_axis=0, concat_axis=0)
            flat = recv[part, offset]
            validity = None
            if c.validity is not None:
                vrecv = jax.lax.all_to_all(c.validity[src], axis_name,
                                           split_axis=0, concat_axis=0)
                validity = jnp.where(in_range, vrecv[part, offset], False)
            out_cols.append(ColVal(c.dtype, flat, validity))
    out_cols = _widen_wire_cols(out_cols, narrowed)
    if with_overflow:
        return out_cols, total, jnp.any(counts > slot)
    return out_cols, total


def _exchange_ragged(sorted_cols, plan, counts, recv_counts, starts,
                     capacity, axis_name: str, num_parts: int,
                     rp: RaggedPlan, with_overflow: bool):
    """Ragged exchange body: base all_to_all at the cold slot plus one
    collective-permute round per partial permutation of hot pairs.
    Every shard traces the same program (SPMD); per-shard differences
    ride static tables indexed by ``axis_index``.  A slice exceeding
    its static limit (base + surplus for hot pairs, base for cold)
    raises the overflow flag — the caller's full-capacity re-run rung,
    rows are never dropped."""
    base, sur = rp.base_slot, rp.surplus_slot
    me = jax.lax.axis_index(axis_name)
    cap_out = rp.out_capacity

    total = recv_counts.sum()
    recv_starts = jnp.concatenate(
        [jnp.zeros(1, dtype=jnp.int32),
         jnp.cumsum(recv_counts)[:-1].astype(jnp.int32)])
    pos = jnp.arange(cap_out, dtype=jnp.int32)
    part = jnp.searchsorted(recv_starts, pos, side="right") - 1
    part = jnp.clip(part, 0, num_parts - 1)
    offset = jnp.clip(pos - recv_starts[part], 0, base + sur - 1)
    in_range = pos < total

    # base payloads: the uniform wire at the COLD slot
    j = jnp.arange(base, dtype=jnp.int32)[None, :]
    src = jnp.clip(starts[:, None] + j, 0, capacity - 1)
    rbase = {g: jax.lax.all_to_all(p, axis_name, split_axis=0,
                                   concat_axis=0)
             for g, p in _pack_payloads(sorted_cols, plan,
                                        sel=src).items()}

    # surplus rounds: each round is a partial permutation; a shard not
    # in the round still traces the (garbage) buffer but the
    # collective-permute transmits only the named links
    sur_rounds = {g: [] for g in rbase}
    jj = jnp.arange(sur, dtype=jnp.int32)
    for r, rnd in enumerate(rp.rounds):
        my_dst = jnp.asarray(rp.round_dst_by_src[r])[me]
        sel = jnp.clip(starts[my_dst] + base + jj, 0, capacity - 1)
        perm = [tuple(p) for p in rnd]
        for g, q in _pack_payloads(sorted_cols, plan, sel=sel).items():
            sur_rounds[g].append(jax.lax.ppermute(q, axis_name, perm=perm))

    # receive: offset < base reads the all_to_all slice; beyond it, the
    # surplus buffer of the (src -> me) pair via the static round table
    my_rounds = jnp.asarray(rp.round_for_src)[me]     # [n_src]
    sur_round = my_rounds[part]
    so = jnp.clip(offset - base, 0, sur - 1)

    def combine(rbase, rounds_list):
        if rounds_list:
            stacked = jnp.stack(rounds_list)          # [rounds, sur, l]
        else:
            stacked = jnp.zeros((1, sur) + rbase.shape[2:], rbase.dtype)
        base_v = rbase[part, jnp.clip(offset, 0, base - 1)]
        sur_v = stacked[sur_round, so]
        pick = (offset < base)
        return jnp.where(pick[:, None], base_v, sur_v)

    flat = {g: combine(r, sur_rounds[g]) for g, r in rbase.items()}
    out_cols = _unpack_payloads(sorted_cols, plan, flat, in_range)
    if with_overflow:
        limits = jnp.asarray(rp.limits)[me]           # [n_dst]
        return out_cols, total, jnp.any(counts > limits)
    return out_cols, total


def exchange_via_gather(cols: Sequence[ColVal], pids: jnp.ndarray, nrows,
                        axis_name: str, num_parts: int,
                        packed: Optional[bool] = None,
                        with_overflow: bool = False,
                        report_site=None,
                        wire_encode: Sequence[int] = ()):
    """Gather-then-redistribute exchange: ONE all_gather per width
    group (rows + their destination ids), then every shard compacts its
    own rows locally — no all_to_all on the wire.  Fewer, larger
    transfers: the DCN-friendly strategy topology-auto picks for axes
    spanning hosts/slices ("Theseus" data-movement shape; see
    docs/performance.md "Topology-aware collective selection").  Slot
    planning does not apply (the gather moves full capacity), so the
    overflow flag is constant-false."""
    from spark_rapids_tpu.columnar import dtypes as dts
    from spark_rapids_tpu.ops import selection
    pid_col = ColVal(dts.INT32, pids.astype(jnp.int32))
    gathered, total = all_gather_cols(
        list(cols) + [pid_col], nrows, axis_name, num_parts,
        packed=packed, report_site=report_site,
        wire_encode=wire_encode)
    out_pids = gathered[-1].values
    me = jax.lax.axis_index(axis_name)
    cap = out_pids.shape[0]
    keep = jnp.logical_and(out_pids == me,
                           jnp.arange(cap, dtype=jnp.int32) < total)
    out_cols, n_mine = selection.compact(list(gathered[:-1]), keep)
    n_mine = n_mine.astype(jnp.int32)
    if with_overflow:
        return out_cols, n_mine, jnp.zeros((), dtype=jnp.bool_)
    return out_cols, n_mine


def all_gather_cols(cols: Sequence[ColVal], nrows, axis_name: str,
                    num_parts: int,
                    packed: Optional[bool] = None,
                    report_site=None,
                    wire_encode: Sequence[int] = ()
                    ) -> Tuple[List[ColVal], jnp.ndarray]:
    """Broadcast-style collective: every shard receives every shard's rows.

    The TPU analog of GpuBroadcastExchangeExec (one-to-all replication,
    SURVEY.md section 2.4 "Exchanges") — except all-gather is symmetric, so
    "broadcast" of a small table costs one collective, no driver round trip.
    Rides the same lane-packed wire format as ``exchange``: one
    ``all_gather`` per width group instead of one per column + mask.
    """
    capacity = cols[0].values.shape[0] if cols else 0
    if packed is None:
        packed = packed_enabled()
    cols, narrowed = _narrow_wire_cols(cols, wire_encode)
    counts = jax.lax.all_gather(nrows, axis_name)  # [num_parts]
    starts = jnp.concatenate([jnp.zeros(1, dtype=jnp.int32),
                              jnp.cumsum(counts)[:-1].astype(jnp.int32)])
    total = counts.sum()
    cap = num_parts * capacity
    pos = jnp.arange(cap, dtype=jnp.int32)
    part = jnp.searchsorted(starts, pos, side="right") - 1
    part = jnp.clip(part, 0, num_parts - 1)
    offset = jnp.clip(pos - starts[part], 0, capacity - 1)
    in_range = pos < total
    plan = _plan_pack(cols) if packed else None
    _record_wire_report(report_site, cols, plan,
                        saved_per_row=4 * len(narrowed))
    if packed and plan is None and cols:
        metrics_for_session().record_fallback()  # see exchange()
    if plan is not None:
        flat = {g: jax.lax.all_gather(p, axis_name)[part, offset]
                for g, p in _pack_payloads(cols, plan).items()}
        return _widen_wire_cols(
            _unpack_payloads(cols, plan, flat, in_range),
            narrowed), total
    out_cols: List[ColVal] = []
    for c in cols:
        g = jax.lax.all_gather(c.values, axis_name)  # [num_parts, capacity]
        flat = g[part, offset]
        validity = None
        if c.validity is not None:
            gv = jax.lax.all_gather(c.validity, axis_name)
            validity = jnp.where(in_range, gv[part, offset], False)
        out_cols.append(ColVal(c.dtype, flat, validity))
    return _widen_wire_cols(out_cols, narrowed), total


# ------------------------------------------------------------- slot planner --

class SlotPlanner:
    """Per-exchange-site all-to-all slot sizing.

    One instance per session (``planner_for_session``), one entry per
    exchange *site* (the consumer's jit signature).  Modes
    (spark.rapids.tpu.shuffle.slot.mode):

    * ``adaptive`` (default) — slots come from the launch's histogram
      max smoothed with a per-site EMA of observed maxima, so the
      power-of-two bucket is STICKY across launches (a stable slot is a
      stable jit-cache key — no recompile churn when data sizes wobble).
      Warm sites may also launch *speculatively*: skip the stats
      hostsync, reuse the cached slot (and bucket LUT), and verify a
      per-shard overflow flag after the launch — at most ONE budgeted
      hostsync per exchange site either way.
    * ``fixed`` — every launch sized from its own histogram only (the
      pre-EMA behavior; recompiles whenever the bucket moves).
    * ``capacity`` — full-capacity padding, always correct,
      ``num_parts``x the useful bytes on the wire (the A/B baseline).

    A speculative overflow multiplies the site's EMA by
    ``slot.overflowGrowth`` and disables speculation until the next
    observed (stats-sized) launch re-arms it.  Warm sites also return
    to the stats-sized path every ``REFRESH_EVERY`` speculative
    launches so the EMA keeps sampling — without the refresh a site
    that once saw a skewed batch would ship its inflated slot forever
    (successful speculative launches observe nothing).
    """

    REFRESH_EVERY = 16

    def __init__(self, mode: str = "adaptive", growth: float = 2.0):
        self.mode = mode
        self.growth = growth
        self._lock = threading.Lock()
        self.sites: Dict[Hashable, dict] = {}

    def plan(self, site: Hashable, max_slice: int, capacity: int) -> int:
        """Slot for a stats-sized launch (histogram max in hand)."""
        if self.mode == "capacity":
            return capacity
        if self.mode == "fixed":
            return pick_slot(max_slice, capacity)
        with self._lock:
            e = self.sites.get(site)
            ema = e["ema"] if e and e.get("capacity") == capacity else 0.0
        if not ema:
            # cold site + cost model: seed the EMA from the persisted
            # rows x skew evidence so a warm START lands in the same
            # power-of-two bucket (= same jit key) as the last process
            from spark_rapids_tpu.plan.costmodel import active_model
            cm = active_model()
            if cm is not None:
                prior = cm.slot_prior(site)
                if 0 < prior <= capacity:
                    ema = float(prior)
        return pick_slot(max(int(max_slice), int(ema)), capacity)

    def observe(self, site: Hashable, max_slice: int, slot: int,
                capacity: int, lut=None, rows: int = 0) -> None:
        """Record a stats-sized launch: update the EMA, cache the slot
        (+ optional bucket LUT) for speculative reuse, clear any
        overflow latch."""
        with self._lock:
            e = self.sites.setdefault(site, {})
            prev = e.get("ema", 0.0)
            e["ema"] = float(max_slice) if not prev else \
                0.7 * prev + 0.3 * float(max_slice)
            e["slot"] = slot
            e["capacity"] = capacity
            e["rows"] = rows
            if lut is not None:
                e["lut"] = lut
            e.pop("overflowed", None)
        from spark_rapids_tpu.utils import tracing
        if tracing._armed and rows:
            # per-site evidence for the observation store (ROADMAP
            # item 3 producer): observed rows, hottest-slice fraction
            # (1.0 = every row in one (src,dst) slice), and — once the
            # exchange body has trace-reported its lane layout — the
            # payload bytes this site moves per launch
            fields = {"rows": float(rows),
                      "skew": round(max_slice / max(rows, 1), 4)}
            rep = wire_report(site)
            if rep:
                fields["bytes"] = float(rows * rep["row_bytes"])
            tracing.observe_site(site, **fields)

    def speculative(self, site: Hashable, capacity: int
                    ) -> Optional[dict]:
        """Steady-state entry for a warm adaptive site (slot + cached
        LUT), or None when the site must run the stats hostsync: cold,
        capacity changed, non-adaptive mode, an unresolved overflow, or
        the periodic EMA refresh (every REFRESH_EVERY warm launches)."""
        if self.mode != "adaptive":
            return None
        with self._lock:
            e = self.sites.get(site)
            if not e or e.get("capacity") != capacity or \
                    e.get("overflowed") or "slot" not in e:
                return None
            e["warm"] = e.get("warm", 0) + 1
            if e["warm"] % self.REFRESH_EVERY == 0:
                return None  # periodic re-observation keeps the EMA live
            return dict(e)

    def observe_overflow(self, site: Hashable) -> None:
        """A speculative slot dropped rows: grow the EMA by the
        configured factor and force the next launch back onto the
        stats-sized path."""
        with self._lock:
            e = self.sites.setdefault(site, {})
            e["overflowed"] = True
            e["ema"] = max(e.get("ema", 0.0) * self.growth,
                           e.get("slot", 8) * self.growth)


_default_planner = SlotPlanner()
_default_metrics = None  # built lazily below


def planner_for_session(session=None) -> SlotPlanner:
    """The session's SlotPlanner (created on first use; mode/growth
    re-read from the conf each call so tests can flip them live).
    Without an active session (bare kernel tests) a process-global
    default planner is shared."""
    if session is None:
        from spark_rapids_tpu.api.session import TpuSession
        session = TpuSession._active
    if session is None:
        return _default_planner
    from spark_rapids_tpu.config import rapids_conf as rc
    p = getattr(session, "shuffle_planner", None)
    if p is None:
        p = SlotPlanner()
        session.shuffle_planner = p
    p.mode = session.conf.get(rc.SHUFFLE_SLOT_MODE)
    p.growth = session.conf.get(rc.SHUFFLE_SLOT_OVERFLOW_GROWTH)
    return p


# ---------------------------------------------------------- wire observability --

class ShuffleWireMetrics:
    """Cumulative shuffle-wire counters (one per session; process-global
    fallback for bare kernel use).  Exchange consumers record each
    launch host-side; per-query deltas land in the QueryEnd ``shuffle``
    dict → eventlog ``QueryInfo.shuffle`` → profiling health checks."""

    FIELDS = ("exchanges", "collectives", "rowsMoved", "rowsUseful",
              "bytesMoved", "slotOverflowRetries", "perColumnFallbacks",
              "raggedExchanges", "encodedBytesSaved", "wireDictBytes",
              "encodableDecodedExchanges", "wireDictFallbacks",
              "fusedWireDispatches", "unfusedWireDispatches")

    def __init__(self):
        self._lock = threading.Lock()
        self.counters = {k: 0 for k in self.FIELDS}
        # per-width-group and per-destination breakdowns (padding is a
        # property of a destination's slot, not of the exchange as a
        # whole — one hot destination must not hide behind the mean)
        self.per_group: Dict[str, Dict[str, int]] = {}
        self.per_dest: Dict[str, Dict[str, int]] = {}
        # payload bytes of the most recently recorded exchange — the
        # launch whose lane buffers are still resident, which is what
        # the transient_wire_bytes HBM reservation should reflect (a
        # query's CUMULATIVE bytes would overstate it several-fold on
        # multi-exchange plans; earlier payloads were already reused)
        self.last_exchange_bytes = 0

    def record_exchange(self, collectives: int, rows_moved: int,
                        rows_useful: int, bytes_moved: int,
                        packed: bool = True, ragged: bool = False,
                        group_bytes: Optional[Dict[str, int]] = None,
                        per_dest=None, encoded_saved: int = 0) -> None:
        with self._lock:
            c = self.counters
            c["exchanges"] += 1
            c["collectives"] += int(collectives)
            c["rowsMoved"] += int(rows_moved)
            c["rowsUseful"] += int(rows_useful)
            c["bytesMoved"] += int(bytes_moved)
            c["encodedBytesSaved"] += int(encoded_saved)
            if ragged:
                c["raggedExchanges"] += 1
            self.last_exchange_bytes = int(bytes_moved)
            if not packed:
                c["perColumnFallbacks"] += 1
            for g, b in (group_bytes or {}).items():
                e = self.per_group.setdefault(
                    g, {"bytesMoved": 0, "rowsMoved": 0})
                e["bytesMoved"] += int(b)
                e["rowsMoved"] += int(rows_moved)
            for d, (wire, useful) in (per_dest or {}).items():
                e = self.per_dest.setdefault(
                    str(d), {"rowsMoved": 0, "rowsUseful": 0})
                e["rowsMoved"] += int(wire)
                e["rowsUseful"] += int(useful)

    def record_overflow(self) -> None:
        with self._lock:
            self.counters["slotOverflowRetries"] += 1

    def record_encodable_decoded(self) -> None:
        """An exchange whose payload carries dictionary-code columns
        ran with wire encoding OFF — bytes that were free to crush
        shipped wide (the profiling health-check signal)."""
        with self._lock:
            self.counters["encodableDecodedExchanges"] += 1

    def record_wire_dict(self, delta_bytes: int, ok: bool) -> None:
        """One dictionary-delta broadcast for an encoded exchange
        launch (ok=False: the delta frame failed verification and the
        launch degraded to the wide wire)."""
        with self._lock:
            self.counters["wireDictBytes"] += int(delta_bytes)
            if not ok:
                self.counters["wireDictFallbacks"] += 1

    def record_fused_dispatch(self, fused: bool) -> None:
        """One distributed-stage launch: ``fused`` means the stage's
        compute and its wire-ready payload came out of ONE program per
        shard (fusion.wire.enabled warm path); unfused launches ran the
        two-dispatch local+exchange sequence.  Bench emits the pair as
        ``fused_wire_dispatches`` per distributed emission."""
        with self._lock:
            key = "fusedWireDispatches" if fused \
                else "unfusedWireDispatches"
            self.counters[key] += 1

    def record_fallback(self) -> None:
        """An exchange that requested the packed wire but traced the
        per-column path (unpackable columns).  Fired at trace time by
        the exchange body itself — once per compiled program."""
        with self._lock:
            self.counters["perColumnFallbacks"] += 1

    def snapshot(self) -> Dict[str, int]:
        with self._lock:
            out = dict(self.counters)
            out["perGroup"] = {g: dict(v)
                               for g, v in self.per_group.items()}
            out["perDestination"] = {d: dict(v)
                                     for d, v in self.per_dest.items()}
            return out

    @staticmethod
    def delta(after: Dict[str, int], before: Dict[str, int]
              ) -> Dict[str, int]:
        out = {}
        for k, v in after.items():
            if isinstance(v, dict):
                b = before.get(k, {}) or {}
                out[k] = {
                    sub: {f: sv.get(f, 0) - b.get(sub, {}).get(f, 0)
                          for f in sv}
                    for sub, sv in v.items()}
            else:
                out[k] = v - before.get(k, 0)
        return out

    @staticmethod
    def summarize(d: Dict[str, int]) -> Dict[str, float]:
        """Attach the derived padding ratios (wire rows / useful rows —
        1.0 is a perfectly dense exchange, ``num_parts`` is
        full-capacity padding): the aggregate, plus the per-width-group
        and per-destination breakdowns when recorded."""
        out = dict(d)
        out["paddingRatio"] = round(
            d.get("rowsMoved", 0) / max(d.get("rowsUseful", 0), 1), 3)
        pd_ = d.get("perDestination") or {}
        if pd_:
            out["paddingRatioPerDestination"] = {
                k: round(v.get("rowsMoved", 0)
                         / max(v.get("rowsUseful", 0), 1), 3)
                for k, v in sorted(pd_.items(), key=lambda kv: int(kv[0]))}
        pg = d.get("perGroup") or {}
        if pg:
            out["perGroupBytes"] = {g: v.get("bytesMoved", 0)
                                    for g, v in sorted(pg.items())}
        saved = d.get("encodedBytesSaved", 0)
        if saved:
            # decoded-wire bytes / encoded-wire bytes (>= 1.0): the
            # headline wire-compression number bench emits
            out["wireCompressionRatio"] = round(
                (d.get("bytesMoved", 0) + saved)
                / max(d.get("bytesMoved", 0), 1), 3)
        return out


def metrics_for_session(session=None) -> ShuffleWireMetrics:
    global _default_metrics
    if session is None:
        from spark_rapids_tpu.api.session import TpuSession
        session = TpuSession._active
    if session is None:
        if _default_metrics is None:
            _default_metrics = ShuffleWireMetrics()
        return _default_metrics
    m = getattr(session, "shuffle_metrics", None)
    if m is None:
        m = ShuffleWireMetrics()
        session.shuffle_metrics = m
    return m


class WireDictBroadcast:
    """Once-per-exchange dictionary DELTA broadcast for the compressed
    wire (one instance per session).

    An encoded exchange ships i32 codes; the receive side's eventual
    decode needs the dictionary.  On a single-controller mesh the
    dictionary is host-shared, so what actually moves is the DELTA —
    the entries this exchange *site* has not broadcast yet — and this
    registry makes that edge real: the delta serializes through the
    shared frame codec (a real compressed payload, accounted as
    ``wireDictBytes``), passes the ``shuffle.wire.dict`` fire_mutate
    chaos point, and round-trips with a crc32 gate.  A delta frame
    that fails verification degrades THAT launch to the wide
    (unnarrowed) wire, emits a typed ``EncodedWireInvalid`` event, and
    resets the site so the next launch rebroadcasts the full
    dictionary — exact results either way, never wrong bytes."""

    def __init__(self):
        self._lock = threading.Lock()
        # site -> per-column (entries_broadcast, crc_of_those_entries,
        # last_seen_list): holding the REFERENCE (not id() — CPython
        # recycles addresses after GC) lets the steady-state launch
        # (same dictionary object, nothing appended) skip hashing
        # entirely, and the crc chains incrementally over the delta —
        # per-launch host work is O(delta), not O(dictionary)
        self.sent: Dict[Hashable, List[tuple]] = {}

    @staticmethod
    def _crc(entries, start: int = 0) -> int:
        import zlib
        crc = start
        for v in entries:
            b = b"\x00" if v is None else v.encode("utf-8")
            crc = zlib.crc32(len(b).to_bytes(4, "big") + b, crc)
        return crc & 0xFFFFFFFF

    def broadcast(self, site, dicts, codec_level: int = 2
                  ) -> Tuple[int, bool]:
        """(delta_bytes, ok) for one encoded launch at ``site`` over
        the exchange's code-column dictionaries."""
        import numpy as np
        import zlib
        from spark_rapids_tpu import native
        from spark_rapids_tpu.robustness.inject import fire_mutate
        with self._lock:
            state = self.sent.get(site)
            if state is None or len(state) != len(dicts):
                state = [(0, 0, None)] * len(dicts)
            deltas = []
            new_state = []
            for (n_sent, crc_sent, last_ref), d in zip(state, dicts):
                if last_ref is d and n_sent <= len(d):
                    # the SAME append-only list: identity proves the
                    # sent prefix unchanged — no prefix re-hash; an
                    # unchanged length is a zero-cost empty delta
                    if len(d) == n_sent:
                        deltas.append([])
                        new_state.append((n_sent, crc_sent, last_ref))
                        continue
                elif n_sent > len(d) or \
                        self._crc(d[:n_sent]) != crc_sent:
                    # the dictionary diverged from what this site
                    # already broadcast (a different query's dict at
                    # the same site): full rebroadcast
                    n_sent, crc_sent = 0, 0
                deltas.append(list(d[n_sent:]))
                # chain the crc over ONLY the delta entries
                new_state.append((len(d),
                                  self._crc(d[n_sent:], crc_sent), d))
        flat = [v for delta in deltas for v in delta]
        payload = b"\x00".join(
            b"\x01" if v is None else v.encode("utf-8") for v in flat)
        want_crc = zlib.crc32(payload) & 0xFFFFFFFF
        blob = b""
        ok = True
        if payload:
            blob = native.serialize_batch(
                1, [(0, np.frombuffer(payload, dtype=np.uint8), None,
                     None)], compress=codec_level)
            blob = fire_mutate("shuffle.wire.dict", blob)
            try:
                _, cols = native.deserialize_batch(blob)
                got = cols[0][1]
                got_crc = zlib.crc32(
                    b"" if got is None else got.tobytes()) & 0xFFFFFFFF
                ok = got_crc == want_crc
            except Exception:
                ok = False
        with self._lock:
            if ok:
                self.sent[site] = new_state
            else:
                # force a full rebroadcast next launch; this launch
                # ships wide
                self.sent.pop(site, None)
        return len(blob), ok


_default_wire_dicts: Optional[WireDictBroadcast] = None


def wire_dicts_for_session(session=None) -> WireDictBroadcast:
    global _default_wire_dicts
    if session is None:
        from spark_rapids_tpu.api.session import TpuSession
        session = TpuSession._active
    if session is None:
        if _default_wire_dicts is None:
            _default_wire_dicts = WireDictBroadcast()
        return _default_wire_dicts
    w = getattr(session, "wire_dicts", None)
    if w is None:
        w = WireDictBroadcast()
        session.wire_dicts = w
    return w


def broadcast_wire_dicts(site, dicts, metrics) -> bool:
    """Consumer-side helper: run the dictionary-delta broadcast for an
    encoded launch, account the bytes, and on a failed verification
    emit the typed event and report False (the caller launches the
    wide-wire program variant)."""
    if not dicts:
        return True
    from spark_rapids_tpu import native
    delta_bytes, ok = wire_dicts_for_session().broadcast(
        site, dicts, codec_level=native.frame_codec_level())
    metrics.record_wire_dict(delta_bytes, ok)
    if not ok:
        from spark_rapids_tpu.utils.events import emit_on_session
        emit_on_session("EncodedWireInvalid", site=str(site),
                        deltaBytes=delta_bytes)
    return ok


def wire_row_bytes(dtypes, nullable: Optional[int] = None) -> int:
    """Estimated wire bytes per row for a column set (data lanes plus
    bit-packed validity; ``nullable`` defaults to every column, an
    upper bound — exact nullability is a trace-time property)."""
    import numpy as np
    data = sum(max(np.dtype(dt.storage).itemsize, 1) for dt in dtypes)
    n = len(dtypes) if nullable is None else nullable
    return data + (n + 7) // 8


def estimate_collectives(dtypes, packed: bool,
                         nullable: Optional[int] = None) -> int:
    """Collectives one exchange launches: the counts vector plus one per
    width group (packed) or one per column + validity mask (fallback)."""
    import numpy as np
    n = len(dtypes) if nullable is None else nullable
    if not packed:
        return 1 + len(dtypes) + n
    storages = [np.dtype(dt.storage) for dt in dtypes]
    has64 = any(st == np.float64 for st in storages)
    has32 = any(st.itemsize in (4, 8) and st != np.float64
                for st in storages)
    has8 = any(st.itemsize in (1, 2) for st in storages) or n > 0
    return 1 + int(has32) + int(has8) + int(has64)


def record_exchange_metrics(metrics: ShuffleWireMetrics, *, dtypes,
                            slot: int, num_parts: int, nshards: int,
                            rows_useful: int, packed: bool,
                            nullable: Optional[int] = None,
                            site=None, exchanges: int = 1,
                            ragged: Optional[RaggedPlan] = None,
                            counts=None,
                            wire_encode_cols: int = 0) -> None:
    """One consumer-side accounting call per exchange launch: wire rows
    are the padded slots every shard puts on ICI (for a ragged plan,
    the base slots plus each surplus pair's one transmitted buffer);
    useful rows come from the site's histogram (or the planner's last
    observation on speculative launches).  When the site's compiled
    program recorded its trace-time lane report (``report_site`` on the
    exchange), the EXACT collective count and row bytes are used; the
    all-nullable static estimate only covers launches before first
    trace.  ``counts`` (the [src, dst] histogram, when materialized)
    feeds the per-destination padding breakdown."""
    import numpy as np
    rep = None
    if ragged is not None:
        rep = wire_report(_ragged_site(site, ragged))
        if rep is not None and rep.get("fallback"):
            # the compiled program fell back to the uniform wire (the
            # lane packer refused the columns — exchange() takes the
            # ragged branch only when packing succeeds): account the
            # program that actually moved bytes.  The exchange body
            # marks the RAGGED report key ``fallback`` at trace time;
            # that breadcrumb is the ONLY valid evidence — the plain
            # -site report may belong to a different variant compiled
            # at the same signature (e.g. a uniform-slot session), and
            # accounting runs before the launch, so a first launch
            # trusts the caller's plan until the program traces.
            # Callers that sized the program from the plan pass slot=0;
            # the fallback program ran at the plan's base+surplus
            # upper bound.
            slot = slot or (ragged.base_slot + ragged.surplus_slot)
            ragged = None
    if ragged is not None:
        rows_moved = ragged.wire_rows(nshards) * exchanges
    else:
        rows_moved = nshards * num_parts * slot * exchanges
        if rep is None:
            rep = wire_report(site)
    if rep is not None:
        collectives = rep["collectives"]
        row_bytes = rep["row_bytes"]
        group_row_bytes = rep.get("group_row_bytes", {})
        saved_pr = rep.get("row_bytes_saved", 0)
    else:
        collectives = estimate_collectives(dtypes, packed, nullable)
        # pre-trace estimate: each wire-encoded int64 code column ships
        # one i32 lane instead of two
        saved_pr = 4 * int(wire_encode_cols)
        row_bytes = max(wire_row_bytes(dtypes, nullable) - saved_pr, 0)
        group_row_bytes = {}
    if group_row_bytes:
        group_bytes = {g: rows_moved * rb
                       for g, rb in group_row_bytes.items()}
    else:
        group_bytes = {"percol": rows_moved * row_bytes}
    per_dest = None
    if counts is not None:
        counts = np.asarray(counts)
        per_dest = {}
        for d in range(counts.shape[1]):
            if ragged is not None:
                pairs_to_d = sum(1 for _, dd in ragged.pairs if dd == d)
                wire = (nshards * ragged.base_slot
                        + pairs_to_d * ragged.surplus_slot) * exchanges
            else:
                wire = nshards * slot * exchanges
            per_dest[d] = (wire, int(counts[:, d].sum()) * exchanges)
    metrics.record_exchange(
        collectives=collectives * exchanges,
        rows_moved=rows_moved,
        rows_useful=int(rows_useful),
        bytes_moved=rows_moved * row_bytes,
        packed=packed, ragged=ragged is not None,
        group_bytes=group_bytes, per_dest=per_dest,
        encoded_saved=rows_moved * saved_pr)
