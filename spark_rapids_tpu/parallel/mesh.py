"""Device mesh management and multi-host fleet membership.

The reference's distributed substrate is one GPU per Spark executor connected
by UCX (shuffle-plugin/, SURVEY.md section 2.5).  The TPU substrate is a
``jax.sharding.Mesh`` over the pod slice: shuffle partitions map onto mesh
shards and exchange rides ICI collectives instead of UCX point-to-point.

One mesh axis ("data") is enough for the SQL workload: all reference
parallelism is data parallelism over partitions (SURVEY.md section 2.5
"Parallelism strategies").

Three host notions layer on top of the device mesh:

- **Multi-controller fleet** (``init_fleet``): N processes — one per
  host — each contribute their local devices to one global mesh via
  ``jax.distributed.initialize``; collectives across the process
  boundary ride DCN.  ``device_host`` is the device's process index.
- **Logical hosts** (``assign_logical_hosts``): a SINGLE-process mesh
  partitioned into simulated hosts so the fleet machinery — DCN
  collective selection, deadline scaling, membership, the shrink rung
  — is testable under tier-1 without real multi-process bring-up.
- **Membership** (``HostMembership``): a file-backed per-host beat
  registry.  Hosts beat at ``heartbeatMs``; a peer silent past
  ``heartbeatMs * missedBeatsFatal`` is declared lost (HostLoss event
  + retryable ``HostLossFault``), which the recovery ladder answers
  with its shrink rung (``surviving_mesh``).
"""

from __future__ import annotations

import json
import os
import tempfile
import time
from typing import Dict, List, Optional, Set

import numpy as np

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

DATA_AXIS = "data"


def shard_map(f, *, mesh: Mesh, in_specs, out_specs, check_vma=False):
    """``jax.shard_map`` with the engine's default ``check_vma=False``."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)


def make_mesh(n_devices: Optional[int] = None,
              axis_name: str = DATA_AXIS) -> Mesh:
    devices = jax.devices()
    if n_devices is not None:
        if len(devices) < n_devices:
            raise ValueError(
                f"need {n_devices} devices, have {len(devices)}")
        devices = devices[:n_devices]
    return Mesh(np.array(devices), (axis_name,))


# ------------------------------------------------- host identification --

# device id -> simulated host index, set by assign_logical_hosts on a
# single-process mesh.  Empty means hosts = processes (the real fleet
# mapping, and the trivial single-host mapping for one process).
_LOGICAL_HOST_BY_DEVICE: Dict[int, int] = {}


def assign_logical_hosts(mesh: Mesh, n_hosts: int) -> None:
    """Partition ``mesh``'s devices into ``n_hosts`` contiguous
    simulated hosts (spark.rapids.tpu.fleet.logicalHosts).  Ignored in
    real multi-controller mode — process boundaries define hosts there
    and pretending otherwise would misclassify real DCN links."""
    if is_multi_controller():
        return
    _LOGICAL_HOST_BY_DEVICE.clear()
    devs = list(mesh.devices.flat)
    if n_hosts <= 1 or len(devs) < 2:
        return
    n_hosts = min(n_hosts, len(devs))
    per = -(-len(devs) // n_hosts)  # ceil
    for i, d in enumerate(devs):
        _LOGICAL_HOST_BY_DEVICE[d.id] = min(i // per, n_hosts - 1)


def clear_logical_hosts() -> None:
    _LOGICAL_HOST_BY_DEVICE.clear()


def device_host(device) -> int:
    """Which host owns ``device``: the logical-host assignment when one
    is active, else the device's controller process."""
    if _LOGICAL_HOST_BY_DEVICE:
        return _LOGICAL_HOST_BY_DEVICE.get(
            device.id, getattr(device, "process_index", 0))
    return getattr(device, "process_index", 0)


def mesh_hosts(mesh: Mesh) -> List[int]:
    """Sorted distinct hosts owning this mesh's devices."""
    return sorted({device_host(d) for d in mesh.devices.flat})


def is_multi_controller() -> bool:
    """True in a real multi-controller fleet (>1 jax process)."""
    try:
        return jax.process_count() > 1
    except RuntimeError:
        return False


def axis_link_kind(mesh: Mesh, axis_name: Optional[str] = None) -> str:
    """Link class of one mesh axis: ``"ici"`` when every device on the
    axis lives on one host AND one pod slice (chip-to-chip
    interconnect — all_to_all is cheap), ``"dcn"`` when the axis spans
    hosts or slices (data-center network — prefer fewer, larger
    transfers: gather-then-redistribute).  "Host" means the controller
    process in a real fleet, or the logical-host assignment on a
    simulated one; the plain virtual CPU mesh used by tests/dryruns is
    single-host single-slice, so it reads "ici" and topology-auto
    keeps today's collective selection."""
    axis_name = axis_name or mesh.axis_names[0]
    try:
        ax = mesh.axis_names.index(axis_name)
    except ValueError:
        return "ici"
    # representative devices along this axis, other axes fixed at 0
    idx = [0] * mesh.devices.ndim
    devs = []
    for i in range(mesh.devices.shape[ax]):
        idx[ax] = i
        devs.append(mesh.devices[tuple(idx)])
    hosts = {device_host(d) for d in devs}
    slices = {getattr(d, "slice_index", 0) for d in devs}
    return "dcn" if len(hosts) > 1 or len(slices) > 1 else "ici"


def topology(mesh: Mesh) -> dict:
    """Topology metadata for planner/metrics consumption: per-axis link
    kinds plus device and host counts (docs/performance.md
    "Topology-aware collective selection")."""
    return {"devices": int(mesh.devices.size),
            "hosts": len(mesh_hosts(mesh)),
            "axes": {name: axis_link_kind(mesh, name)
                     for name in mesh.axis_names}}


def surviving_mesh(mesh: Mesh, lost_hosts: Set[int]) -> Mesh:
    """Rebuild ``mesh`` over the devices of hosts NOT in
    ``lost_hosts`` — the shrink rung's new layout.  Raises ValueError
    when nothing survives (the ladder then escalates past shrink)."""
    keep = [d for d in mesh.devices.flat
            if device_host(d) not in lost_hosts]
    if not keep:
        raise ValueError("no surviving hosts to rebuild the mesh over")
    return Mesh(np.array(keep), mesh.axis_names[:1])


def shard_spec(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, PartitionSpec(mesh.axis_names[0]))


def replicated_spec(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, PartitionSpec())


# ------------------------------------------------ multi-controller init --

# jax.distributed may initialize exactly once per process; remember the
# bring-up so a second session in the same process reuses it
_FLEET_STATE: Dict[str, object] = {"initialized": False}


def init_fleet(coordinator: str, process_id: int, num_processes: int,
               timeout_s: int = 60) -> bool:
    """Multi-controller bring-up: join ``coordinator``'s fleet as
    process ``process_id`` of ``num_processes`` via
    ``jax.distributed.initialize``.  Returns True when this process is
    part of a live multi-controller fleet, False for single-controller
    configs (empty coordinator / num_processes < 2).  Idempotent — jax
    allows one initialize per process, so a second session reuses the
    standing bring-up (and mismatched coordinates raise)."""
    if not coordinator or num_processes < 2:
        return False
    if _FLEET_STATE["initialized"]:
        prev = (_FLEET_STATE["coordinator"], _FLEET_STATE["process_id"],
                _FLEET_STATE["num_processes"])
        if prev != (coordinator, process_id, num_processes):
            raise RuntimeError(
                f"fleet already initialized as {prev}, cannot re-join "
                f"as {(coordinator, process_id, num_processes)}")
        return True
    if process_id < 0:
        raise ValueError("fleet.processId must be set (>= 0) when "
                         "fleet.coordinator is configured")
    # the CPU backend's cross-process collectives need gloo selected
    # BEFORE initialize (the env-var spelling the old multihost worker
    # used does not exist — the since-seed env-fail)
    platforms = os.environ.get("JAX_PLATFORMS", "")
    if "cpu" in platforms.split(","):
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(coordinator,
                               num_processes=num_processes,
                               process_id=process_id,
                               initialization_timeout=timeout_s)
    _FLEET_STATE.update(initialized=True, coordinator=coordinator,
                        process_id=process_id,
                        num_processes=num_processes)
    return True


def shutdown_fleet() -> None:
    """Tear down the multi-controller runtime.  Required on the CPU
    test fleet: a non-coordinator process that exits without shutdown
    hangs in the distributed client's destructor."""
    if not _FLEET_STATE["initialized"]:
        return
    _FLEET_STATE["initialized"] = False
    try:
        jax.distributed.shutdown()
    except Exception:
        pass  # already torn down / coordinator gone


# ----------------------------------------------- host<->device transfer --

def host_put(mesh: Mesh, host_array, sharded: bool = True):
    """Build a device array from identical per-process host data.  In
    single-controller mode this is ``jnp.asarray`` (today's behavior:
    uncommitted, downstream jit shards it).  In a multi-controller
    fleet a plain ``jnp.asarray`` would be a PROCESS-LOCAL array that
    cannot enter a global computation — instead every process, holding
    the same full host copy, contributes its addressable shards via
    ``make_array_from_callback`` under the global mesh."""
    import jax.numpy as jnp
    if not is_multi_controller():
        return jnp.asarray(host_array)
    host_array = np.asarray(host_array)
    spec = shard_spec(mesh) if sharded and host_array.ndim and \
        host_array.shape[0] % mesh.devices.size == 0 \
        else replicated_spec(mesh)
    return jax.make_array_from_callback(
        host_array.shape, spec, lambda idx: host_array[idx])


def to_host(x) -> np.ndarray:
    """Fetch ``x`` to a full host copy.  Addressable arrays (all of
    single-controller) are a plain ``np.asarray``; a multi-controller
    global array holds only local shards per process, so replicate it
    across the fleet first (jit identity into a replicated layout,
    with ``process_allgather`` as the fallback for inputs jit won't
    take)."""
    if isinstance(x, jax.Array) and not x.is_fully_addressable:
        mesh = getattr(getattr(x, "sharding", None), "mesh", None)
        if mesh is not None:
            try:
                def replicate_to_host(a):
                    return a
                rep = jax.jit(replicate_to_host,
                              out_shardings=replicated_spec(mesh))(x)
                return np.asarray(rep)
            except Exception:
                pass
        from jax.experimental import multihost_utils
        return np.asarray(multihost_utils.process_allgather(
            x, tiled=True))
    return np.asarray(x)


# ------------------------------------------------------ host membership --

def membership_dir(conf_dir: str, coordinator: str) -> str:
    """Resolve the beat-registry directory: the configured one, else a
    temp-dir path keyed by coordinator so one fleet's hosts agree on a
    location without config plumbing (CPU test fleets)."""
    if conf_dir:
        return conf_dir
    key = (coordinator or "local").replace(":", "_").replace("/", "_")
    return os.path.join(tempfile.gettempdir(),
                        f"sr_tpu_fleet_{key}")


class HostMembership:
    """File-backed per-host liveness registry: each host atomically
    rewrites its own ``host-<id>.json`` beat record (wall-clock ``ts``
    plus pid); everyone reads everyone's.  A peer whose record ages
    past ``heartbeat_ms * missed_fatal`` — or that disappears after
    having joined — is declared LOST exactly once: a ``HostLoss``
    event is emitted and ``check()`` raises the retryable
    ``HostLossFault`` that enters the recovery ladder at its shrink
    rung.  File-backed keeps the registry coordinator-free on CPU test
    meshes and logical-host fleets; a real fleet points
    ``fleet.membershipDir`` at shared storage.

    Every ``beat()`` runs through the ``fleet.heartbeat`` injection
    point, so the chaos suite can silence a host (raise) or stall it
    (delay) exactly where a real network partition would."""

    def __init__(self, dirpath: str, host_id: int, n_hosts: int,
                 heartbeat_ms: int = 500, missed_fatal: int = 3,
                 session=None):
        from spark_rapids_tpu.robustness import inject
        from spark_rapids_tpu.robustness.faults import HostLossFault
        inject.register_point("fleet.heartbeat", HostLossFault)
        self.dir = dirpath
        self.host = int(host_id)
        self.n_hosts = int(n_hosts)
        self.heartbeat_ms = int(heartbeat_ms)
        self.missed_fatal = int(missed_fatal)
        self._session = session
        self.lost: Set[int] = set()
        self._seen: Set[int] = set()
        self._last_beat = 0.0
        self._joined = False
        # last successfully-parsed record per peer: a beat file whose
        # CONTENT is torn/corrupt (external corruption — the atomic
        # tmp+fsync+replace write itself never publishes a torn
        # record) must not read as "vanished after join"; the peer is
        # judged by its last good timestamp until a fresh record lands
        self._last_rec: Dict[int, dict] = {}
        os.makedirs(dirpath, exist_ok=True)

    @property
    def _tracker(self):
        """The session's gray-failure health tracker (None unless
        fleet.grayFailure.enabled) — beat records gossip local walls
        to it and check() feeds it peers' evidence."""
        return getattr(self._session, "gray_health", None) \
            if self._session is not None else None

    # ----------------------------------------------------------- paths --
    def _path(self, host: int) -> str:
        return os.path.join(self.dir, f"host-{host}.json")

    def _emit(self, event: str, **fields) -> None:
        try:
            from spark_rapids_tpu.utils.events import emit_on_session
            emit_on_session(event, self._session, **fields)
        except Exception:
            pass  # membership must work without an event log

    # ---------------------------------------------------------- beating --
    def beat(self, force: bool = False) -> None:
        """Write this host's beat record (rate-limited to the
        heartbeat period unless ``force``).  The write is atomic with
        the temp+fsync+``os.replace`` discipline used by every other
        durable blob in the engine, so a reader never sees a torn
        record — even across a power cut between the rename and the
        data reaching the platters."""
        now = time.time()
        if not force and (now - self._last_beat) * 1000.0 < \
                self.heartbeat_ms:
            return
        from spark_rapids_tpu.robustness import inject
        inject.fire("fleet.heartbeat")
        rec = {"host": self.host, "pid": os.getpid(),
               "ts": round(now, 3)}
        tracker = self._tracker
        if tracker is not None:
            # gossip this host's per-point walls on the beat record:
            # peers fold them into their health view of us, which is
            # how per-host wall evidence crosses process boundaries
            # without a coordinator
            walls = tracker.local_walls()
            if walls:
                rec["walls"] = walls
        path = self._path(self.host)
        tmp = f"{path}.tmp.{os.getpid()}"
        try:
            with open(tmp, "w", encoding="utf-8") as f:
                json.dump(rec, f)
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, path)
        except OSError:
            return  # a missed write is just a missed beat
        self._last_beat = now
        if not self._joined:
            self._joined = True
            self._emit("HostJoin", host=self.host, pid=os.getpid(),
                       hosts=self.n_hosts)

    # --------------------------------------------------------- checking --
    def _read(self, host: int) -> Optional[dict]:
        """Parse ``host``'s beat record.  A MISSING file is None (the
        vanished-after-join judgment needs it); a file whose content
        is torn or corrupt answers the last successfully-parsed record
        instead — external corruption of the registry must age the
        peer out by silence, never false-kill it on the spot."""
        try:
            with open(self._path(host), encoding="utf-8") as f:
                rec = json.load(f)
            self._last_rec[host] = rec
            return rec
        except FileNotFoundError:
            return None
        except (OSError, ValueError):
            return self._last_rec.get(host)

    def silent_ms(self, host: int) -> Optional[float]:
        """How long since ``host``'s last beat (None = never beat)."""
        rec = self._read(host)
        if rec is None:
            return None
        return max(0.0, (time.time() - float(rec.get("ts", 0))) * 1000.0)

    def check(self, raise_on_loss: bool = True) -> Set[int]:
        """Beat, then judge every peer.  A peer is lost when its beat
        record aged past the fatal window, or vanished after having
        joined.  A peer that never beat is merely not-yet-joined —
        bring-up must not read as death.  Newly-lost hosts emit
        ``HostLoss`` once; with ``raise_on_loss`` the first loss
        raises ``HostLossFault`` so the caller's recovery ladder takes
        over.  Returns the full lost set."""
        self.beat()
        fatal_ms = float(self.heartbeat_ms * self.missed_fatal)
        tracker = self._tracker
        newly = []
        for h in range(self.n_hosts):
            if h == self.host or h in self.lost:
                continue
            rec = self._read(h)
            if rec is None:
                if h in self._seen:
                    newly.append((h, fatal_ms))  # joined, then vanished
                continue
            self._seen.add(h)
            if tracker is not None:
                # gray-failure evidence: the peer's achieved beat
                # interval (jitter shows a fail-slow writer long
                # before fatal silence) plus its gossiped walls
                tracker.observe_beat(h, float(rec.get("ts", 0)))
                tracker.observe_peer_walls(h, rec.get("walls"))
            silent = max(0.0, (time.time() -
                               float(rec.get("ts", 0))) * 1000.0)
            if silent > fatal_ms:
                newly.append((h, silent))
        for h, silent in newly:
            self.lost.add(h)
            self._emit("HostLoss", host=h, silentMs=round(silent, 1),
                       missed=self.missed_fatal)
        if newly and raise_on_loss:
            from spark_rapids_tpu.robustness.faults import HostLossFault
            h, silent = newly[0]
            raise HostLossFault(
                note=f"host {h} silent {silent:.0f}ms "
                     f"(> {self.heartbeat_ms}ms x {self.missed_fatal})",
                host=h)
        return set(self.lost)

    def alive_hosts(self) -> List[int]:
        return [h for h in range(self.n_hosts) if h not in self.lost]

    def rejoin(self, host: int) -> None:
        """Readmit a previously-lost (or quarantined) host: drop it
        from the lost set and from the seen set, so a host whose
        record has not re-appeared yet reads as not-yet-joined (never
        instantly re-lost as vanished-after-join) and fresh evidence
        starts clean."""
        self.lost.discard(host)
        self._seen.discard(host)
        self._last_rec.pop(host, None)

    # ------------------------------------------------------ test levers --
    def simulate_loss(self, host: int) -> None:
        """Age ``host``'s beat record past the fatal window — the test
        stand-in for a crashed/partitioned peer."""
        rec = self._read(host) or {"host": host, "pid": 0}
        rec["ts"] = time.time() - (self.heartbeat_ms *
                                   self.missed_fatal * 10) / 1000.0
        self._seen.add(host)
        try:
            with open(self._path(host), "w", encoding="utf-8") as f:
                json.dump(rec, f)
        except OSError:
            pass

    def leave(self) -> None:
        """Withdraw this host's beat record (clean shutdown — peers
        see an orderly age-out, tests see a clean dir)."""
        try:
            os.unlink(self._path(self.host))
        except OSError:
            pass
