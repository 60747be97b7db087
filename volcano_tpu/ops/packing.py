"""Snapshot → device tensor packing.

This is the TPU-native replacement for the reference's snapshot marshaling
(pkg/scheduler/cache/cache.go:712-790): instead of deep-copying Go structs,
the session is packed into dense arrays the kernels consume.

Layout (R = resource axis = [cpu_milli, memory_MiB, *scalars]; the memory
lane is packed in MiB so float32 stays integer-exact up to 16-PiB nodes —
byte counts above 2^24 would lose precision and break the host score
goldens.  Non-MiB-aligned byte values round and are flagged):
  task_resreq[T, R]   f32   task InitResreq lanes
  task_job[T]         i32   job index per task
  task_sel_bits[T, W] u32   required node-label bits (selector + required affinity)
  task_tol_bits[T, W] u32   tolerated taint bits
  node_idle[N, R]     f32   node Idle lanes
  node_used[N, R]     f32   node Used lanes
  node_alloc[N, R]    f32   node Allocatable lanes
  node_label_bits[N,W]u32   node label bits
  node_taint_bits[N,W]u32   node NoSchedule/NoExecute taint bits
  node_ok[N]          bool  ready & schedulable
  node_task_count[N]  i32 / node_max_tasks[N] i32
  job_min_available[J]i32 / job_ready_count[J] i32

Hard topology spread (PodTopologySpread ``DoNotSchedule``) packs as K
constraint classes — distinct (namespace, selector, topology key,
maxSkew, node eligibility) — by :func:`pack_spread`:
  spread_key_dom[Kk, N]  i32  domain index of each node per topology key
  spread_node_elig[K, N] bool nodes that count toward the class's domains
  spread_dom_count[K, D] i32  matching pods per domain at pack time
  task_spread_on[T, K]   bool the classes each task row is constrained by
  task_spread_self[T, K] bool the classes each task row counts toward
Required pod anti-affinity packs into the same planes as classes of the
absolute kind (``spread_absolute[K]``): a pod's own term (the pods that
match it per domain), and a placed or pending pod's term that a row
matches (the pods that hold it per domain, the symmetry rule).  A row
passes such a class where its domain's count is at most the bound, 0,
rather than where it exceeds the minimum by at most maxSkew.

Label/taint relational predicates become pointwise bitset ops (SURVEY §7
"predicate expressiveness"): W words of 32 bits each; the registry assigns a
bit per distinct (key,value) label pair / taint referenced in the session.
Shapes are padded to buckets to avoid per-session recompiles.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

from volcano_tpu.api import JobInfo, NodeInfo, TaskInfo
from volcano_tpu.api.resource import MIN_MEMORY, MIN_MILLI_CPU, MIN_MILLI_SCALAR

#: Default bitset width: 2 words = 64 distinct label pairs / taints.
DEFAULT_BIT_WORDS = 2

#: Memory lane quantization (bytes per MiB).
MIB = float(1 << 20)

#: Spread and anti-affinity classes one pack encodes for the kernels; a
#: row whose constraints need more keeps host validation.
SPREAD_MAX_CLASSES = 8


class BitRegistry:
    """Assigns bit indices to distinct keys; overflow falls back to host."""

    def __init__(self, words: int = DEFAULT_BIT_WORDS):
        self.words = words
        self.index: Dict[Tuple, int] = {}
        self.overflow = False

    def bit(self, key: Tuple) -> Optional[int]:
        idx = self.index.get(key)
        if idx is None:
            idx = len(self.index)
            if idx >= self.words * 32:
                self.overflow = True
                return None
            self.index[key] = idx
        return idx

    def set_bit(self, arr: np.ndarray, row: int, key: Tuple) -> None:
        idx = self.bit(key)
        if idx is not None:
            arr[row, idx // 32] |= np.uint32(1 << (idx % 32))


def _bucket(n: int, minimum: int = 64) -> int:
    """Round up to the next power-of-two bucket to bound recompiles."""
    if n <= minimum:
        return minimum
    return 1 << math.ceil(math.log2(n))


@dataclass
class PackedSnapshot:
    """Dense session state (numpy host-side; moved to device by the kernel)."""

    # resource axis metadata
    resource_names: List[str] = field(default_factory=list)
    tolerance: np.ndarray = None  # [R]

    # tasks (padded to T_pad; first n_tasks valid)
    n_tasks: int = 0
    task_resreq: np.ndarray = None
    task_job: np.ndarray = None
    task_sel_bits: np.ndarray = None
    task_tol_bits: np.ndarray = None

    # nodes (padded to N_pad; first n_nodes valid)
    n_nodes: int = 0
    node_idle: np.ndarray = None
    node_used: np.ndarray = None
    node_alloc: np.ndarray = None
    node_label_bits: np.ndarray = None
    node_taint_bits: np.ndarray = None
    node_ok: np.ndarray = None
    node_task_count: np.ndarray = None
    node_max_tasks: np.ndarray = None

    # jobs (padded to J_pad; first n_jobs valid)
    n_jobs: int = 0
    job_min_available: np.ndarray = None
    job_ready_count: np.ndarray = None

    # host-side keys for unpacking results
    task_uids: List[str] = field(default_factory=list)
    node_names: List[str] = field(default_factory=list)
    job_uids: List[str] = field(default_factory=list)

    #: True when a relational predicate could not be bitset-encoded
    #: (registry overflow or pod (anti-)affinity present).  jax-allocate
    #: always re-validates the proposed node's predicates host-side, so
    #: such placements degrade to fallbacks rather than wrong bindings;
    #: standalone run_packed callers must post-validate themselves.
    needs_host_validation: bool = False

    #: False when a memory quantity was not MiB-aligned (lane rounds).
    memory_exact: bool = True

    #: True when the label/taint bit registry overflowed — the sel/tol
    #: planes of EVERY row are then suspect, not just flagged tasks.
    #: Host bookkeeping (the explain synthesis gate); not serialized.
    registry_overflow: bool = False

    #: [T] bool — tasks carrying preferred (anti-)affinity terms the kernel
    #: cannot score; jax-allocate routes these to the host path.
    task_has_preferences: np.ndarray = None

    #: [T] bool — per-row needs_host_validation contribution (the OR of
    #: this plus registry overflow is ``needs_host_validation``).  Host
    #: bookkeeping for the warm packer; not serialized.
    task_needs_host: np.ndarray = None

    # ---- warm-cycle metadata (volcano_tpu/ops/pack_cache.py) ----
    #: identity of the producing PackCache (None for cold one-shot packs);
    #: device stagers key their persistent buffers on it.  NOT serialized
    #: (the journal carries the fully materialized arrays, so
    #: trace.replay.verify is delta-blind).
    cache_key: Optional[str] = None
    #: monotonically increasing pack revision within the cache_key
    rev: int = 0
    #: PackDelta describing which rows changed since ``rev - 1``; None on
    #: cold packs and whenever the cache invalidated wholesale
    delta: Optional[object] = None
    #: optional {plane name → device array} mirror staged ahead of the
    #: kernel call (ops/device_stage.py); consumers fall back to the
    #: numpy planes when absent
    device_planes: Optional[Dict[str, object]] = None

    # ---- hard topology spread (pack_spread; None when no row has any) ----
    #: [Kk, N_pad] i32 — each node's domain under each topology key, -1
    #: where the node lacks the label
    spread_key_dom: Optional[np.ndarray] = None
    #: [K] i32 — the topology key row of each class
    spread_class_key: Optional[np.ndarray] = None
    #: [K] i32 — maxSkew of each class; the bound of an absolute one
    spread_max_skew: Optional[np.ndarray] = None
    #: [K] bool — classes of the absolute kind (anti-affinity): a node
    #: passes where its domain's count is at most the bound; None reads
    #: as none
    spread_absolute: Optional[np.ndarray] = None
    #: [K, N_pad] bool — nodes that count toward the class's domains
    spread_node_elig: Optional[np.ndarray] = None
    #: [K, D_pad] i32 — matching pods per domain when packed
    spread_dom_count: Optional[np.ndarray] = None
    #: [T_pad, K] bool — rows each class constrains
    task_spread_on: Optional[np.ndarray] = None
    #: [T_pad, K] bool — rows that count toward each class once placed
    task_spread_self: Optional[np.ndarray] = None
    #: [T_pad] bool — rows with hard spread constraints or required
    #: inter-pod terms the pack did not encode (host validation);
    #: recomputed by every pack, never carried
    task_spread_host: Optional[np.ndarray] = None
    #: what the last pack_spread counted (rows, classes, ms)
    spread_stats: Optional[Dict[str, float]] = None
    #: the same for the anti-affinity classes (affinity_rows_device /
    #: _host, affinity_classes, affinity_pack_ms)
    affinity_stats: Optional[Dict[str, float]] = None

    @property
    def has_spread(self) -> bool:
        return self.spread_max_skew is not None and self.spread_max_skew.size > 0

    def gang_need(self) -> np.ndarray:
        """[n_tasks] placements each row's job still needs to turn ready
        (minAvailable less its ready tasks), as the kernels read it."""
        jobs = self.task_job[: self.n_tasks]
        return self.job_min_available[jobs].astype(np.int64) - self.job_ready_count[jobs]

    @property
    def episodic(self) -> bool:
        """True when a row can be placed and then rolled back, or skipped
        after its job ended: some job has more than one row, or needs
        more than one placement to turn ready.  Only the episode scans
        (ops/kernels.py, ops/pallas_session.py, native) take such a
        session."""
        if self.n_tasks == 0:
            return False
        jobs = self.task_job[: self.n_tasks]
        return bool(np.bincount(jobs).max() > 1 or (self.gang_need() >= 2).any())

    @property
    def shape_key(self) -> Tuple[int, int, int, int, int]:
        return (
            self.task_resreq.shape[0],
            self.node_idle.shape[0],
            self.job_min_available.shape[0],
            self.task_resreq.shape[1],
            self.task_sel_bits.shape[1],
        )


# ---- journal persistence (volcano_tpu/trace) ----

#: array-valued PackedSnapshot fields, in npz key order
_SNAPSHOT_ARRAYS = (
    "tolerance",
    "task_resreq",
    "task_job",
    "task_sel_bits",
    "task_tol_bits",
    "node_idle",
    "node_used",
    "node_alloc",
    "node_label_bits",
    "node_taint_bits",
    "node_ok",
    "node_task_count",
    "node_max_tasks",
    "job_min_available",
    "job_ready_count",
    "task_has_preferences",
    "spread_key_dom",
    "spread_class_key",
    "spread_max_skew",
    "spread_absolute",
    "spread_node_elig",
    "spread_dom_count",
    "task_spread_on",
    "task_spread_self",
    "task_spread_host",
)

#: scalar/list fields carried in the JSON meta record
_SNAPSHOT_META = (
    "resource_names",
    "n_tasks",
    "n_nodes",
    "n_jobs",
    "task_uids",
    "node_names",
    "job_uids",
    "needs_host_validation",
    "memory_exact",
)

_EXTRA_PREFIX = "__extra__"


def save_snapshot(snap: "PackedSnapshot", path: str, **extras) -> str:
    """Persist a PackedSnapshot to a compressed npz (plus caller extras,
    e.g. the kernel assignment and executor name the trace journal
    records).  Everything round-trips through load_snapshot without
    pickle — arrays verbatim, list/str/bool fields via a JSON side
    record."""
    import json

    payload = {}
    for name in _SNAPSHOT_ARRAYS:
        value = getattr(snap, name)
        if value is not None:
            payload[name] = value
    meta = {name: getattr(snap, name) for name in _SNAPSHOT_META}
    payload["__meta__"] = np.array(json.dumps(meta))
    for key, value in extras.items():
        payload[_EXTRA_PREFIX + key] = np.asarray(value)
    np.savez_compressed(path, **payload)
    return path


def load_snapshot(path: str):
    """Inverse of save_snapshot: (PackedSnapshot, extras dict).  String
    extras come back as 0-d unicode arrays (``str()`` them)."""
    import json

    snap = PackedSnapshot()
    extras = {}
    with np.load(path, allow_pickle=False) as data:
        for key in data.files:
            if key == "__meta__":
                for name, value in json.loads(str(data[key])).items():
                    setattr(snap, name, value)
            elif key.startswith(_EXTRA_PREFIX):
                extras[key[len(_EXTRA_PREFIX):]] = data[key]
            else:
                setattr(snap, key, data[key])
    return snap, extras


def _resource_axis(
    tasks: Sequence[TaskInfo], nodes: Sequence[NodeInfo]
) -> Tuple[List[str], np.ndarray]:
    scalars: List[str] = []
    seen = set()
    for t in tasks:
        for name in t.init_resreq.scalars:
            if name not in seen:
                seen.add(name)
                scalars.append(name)
    for n in nodes:
        for name in n.allocatable.scalars:
            if name not in seen:
                seen.add(name)
                scalars.append(name)
    names = ["cpu", "memory", *scalars]
    tol = np.array(
        [MIN_MILLI_CPU, MIN_MEMORY / MIB] + [MIN_MILLI_SCALAR] * len(scalars),
        dtype=np.float32,
    )
    return names, tol


def _res_vec(res, names: List[str], snap: "PackedSnapshot") -> np.ndarray:
    out = np.zeros(len(names), dtype=np.float32)
    out[0] = res.milli_cpu
    if res.memory % MIB:
        snap.memory_exact = False
    out[1] = res.memory / MIB
    for i, name in enumerate(names[2:], start=2):
        out[i] = res.scalars.get(name, 0.0)
    return out


def alloc_planes(
    snap: "PackedSnapshot",
    R: int,
    W: int,
    T: int,
    N: int,
    J: int,
    T_pad: int,
    N_pad: int,
    J_pad: int,
) -> None:
    """Allocate every plane of a PackedSnapshot zeroed at the given
    padded shapes — the single copy shared by pack_session and the warm
    packer's assembly (ops/pack_cache.py), so a new plane cannot be
    added to one and silently missed by the other."""
    snap.n_tasks, snap.n_nodes, snap.n_jobs = T, N, J
    snap.task_resreq = np.zeros((T_pad, R), dtype=np.float32)
    snap.task_job = np.zeros(T_pad, dtype=np.int32)
    snap.task_sel_bits = np.zeros((T_pad, W), dtype=np.uint32)
    snap.task_tol_bits = np.zeros((T_pad, W), dtype=np.uint32)
    snap.node_idle = np.zeros((N_pad, R), dtype=np.float32)
    snap.node_used = np.zeros((N_pad, R), dtype=np.float32)
    snap.node_alloc = np.zeros((N_pad, R), dtype=np.float32)
    snap.node_label_bits = np.zeros((N_pad, W), dtype=np.uint32)
    snap.node_taint_bits = np.zeros((N_pad, W), dtype=np.uint32)
    snap.node_ok = np.zeros(N_pad, dtype=bool)
    snap.node_task_count = np.zeros(N_pad, dtype=np.int32)
    snap.node_max_tasks = np.zeros(N_pad, dtype=np.int32)
    snap.job_min_available = np.zeros(J_pad, dtype=np.int32)
    # Padded jobs get min_available high so padded tasks never commit.
    snap.job_min_available[J:] = np.iinfo(np.int32).max
    snap.job_ready_count = np.zeros(J_pad, dtype=np.int32)
    snap.task_has_preferences = np.zeros(T_pad, dtype=bool)
    snap.task_needs_host = np.zeros(T_pad, dtype=bool)


def pack_task_bits(
    snap: "PackedSnapshot",
    i: int,
    t: TaskInfo,
    label_reg: BitRegistry,
    taint_reg: BitRegistry,
) -> bool:
    """Selector/affinity/toleration bit packing for one ordered task —
    the single copy shared by the cold pack loop and the warm delta
    packer's dirty-row repack (ops/pack_cache.py), so the two cannot
    drift.  Writes the task's sel/tol bit rows and preference flag into
    ``snap`` at row ``i``; returns True when the task needs host
    validation (affinity richer than the bitset encoding)."""
    needs_host = False
    pod = t.pod
    if pod is None:
        return needs_host
    for k, v in (pod.spec.node_selector or {}).items():
        label_reg.set_bit(snap.task_sel_bits, i, (k, v))
    # Required node affinity: single-term all-In expressions fold into
    # the selector bitset; anything richer flags host validation.
    node_aff = (pod.spec.affinity or {}).get("nodeAffinity") or {}
    req = node_aff.get("requiredDuringSchedulingIgnoredDuringExecution") or {}
    terms = req.get("nodeSelectorTerms") or []
    if len(terms) == 1:
        for e in terms[0].get("matchExpressions") or []:
            if e.get("operator", "In") == "In" and len(e.get("values") or []) == 1:
                label_reg.set_bit(
                    snap.task_sel_bits, i, (e["key"], e["values"][0])
                )
            else:
                needs_host = True
    elif terms:
        needs_host = True
    for tol_ in pod.spec.tolerations or []:
        if tol_.operator == "Exists" and not tol_.key:
            # tolerates everything: set all taint bits
            snap.task_tol_bits[i, :] = np.uint32(0xFFFFFFFF)
        elif tol_.operator == "Exists":
            pass  # keyed Exists resolved in the post-node pass
        else:
            for effect in ("NoSchedule", "NoExecute"):
                if not tol_.effect or tol_.effect == effect:
                    taint_reg.set_bit(
                        snap.task_tol_bits, i, (tol_.key, tol_.value, effect)
                    )
    aff = pod.spec.affinity or {}
    # required anti-affinity is pack_spread's (a class, or a host row);
    # pod affinity and preferred anti-affinity stay on the host
    anti = aff.get("podAntiAffinity") or {}
    if aff.get("podAffinity") or any(
        v for k, v in anti.items() if k != "requiredDuringSchedulingIgnoredDuringExecution"
    ):
        needs_host = True
    node_pref = (aff.get("nodeAffinity") or {}).get(
        "preferredDuringSchedulingIgnoredDuringExecution"
    )
    pod_pref = (aff.get("podAffinity") or {}).get(
        "preferredDuringSchedulingIgnoredDuringExecution"
    ) or (aff.get("podAntiAffinity") or {}).get(
        "preferredDuringSchedulingIgnoredDuringExecution"
    )
    if node_pref or pod_pref or _has_soft_spread(pod):
        # Preference terms (and ScheduleAnyway spread) are scoring the
        # kernel has no lanes for — route to the host path.
        snap.task_has_preferences[i] = True
    return needs_host


def _has_soft_spread(pod) -> bool:
    if not pod.spec.topology_spread_constraints:
        return False
    from volcano_tpu.plugins.util import has_soft_spread

    return has_soft_spread(pod)


def task_exists_tolerations(t: TaskInfo) -> Tuple[Tuple[str, str], ...]:
    """(key, effect) pairs of the task's keyed Exists tolerations — what
    resolve_exists_tolerations matches against the taint registry.  The
    warm packer caches this per row so it can re-resolve only affected
    tasks when a dirty node registers a new taint."""
    pod = t.pod
    if pod is None:
        return ()
    out = []
    for tol_ in pod.spec.tolerations or []:
        if tol_.operator == "Exists" and tol_.key:
            out.append((tol_.key, tol_.effect or ""))
    return tuple(out)


def resolve_exists_tolerations(
    snap: "PackedSnapshot", indexed_tasks, taint_reg: BitRegistry
) -> None:
    """Set tol bits for keyed Exists tolerations against the (complete)
    taint registry, for each ``(row, task)`` in ``indexed_tasks``."""
    for i, t in indexed_tasks:
        pod = t.pod
        if pod is None:
            continue
        for tol_ in pod.spec.tolerations or []:
            if tol_.operator == "Exists" and tol_.key:
                for (k, v, eff), idx in taint_reg.index.items():
                    if k == tol_.key and (not tol_.effect or tol_.effect == eff):
                        snap.task_tol_bits[i, idx // 32] |= np.uint32(1 << (idx % 32))


def pack_node_row(
    snap: "PackedSnapshot",
    i: int,
    n: NodeInfo,
    label_reg: BitRegistry,
    taint_reg: BitRegistry,
    enforce_pod_count: bool,
) -> None:
    """Non-lane node state for one row: ok flag, task counts, label/taint
    bits.  Shared by the cold pack loop and the warm packer's dirty-node
    repack."""
    snap.node_ok[i] = n.ready() and not (
        n.node is not None and n.node.spec.unschedulable
    )
    snap.node_task_count[i] = len(n.tasks)
    # Host semantics: the pod-count limit is the predicates plugin's
    # (max_task_num 0 ⇒ it rejects everything); without that plugin
    # no limit applies.
    snap.node_max_tasks[i] = (
        n.allocatable.max_task_num if enforce_pod_count else np.iinfo(np.int32).max
    )
    if n.node is None:
        return
    for k, v in (n.node.metadata.labels or {}).items():
        # Only label pairs some task references need bits.
        if (k, v) in label_reg.index:
            label_reg.set_bit(snap.node_label_bits, i, (k, v))
    for taint in n.node.spec.taints or []:
        if taint.effect in ("NoSchedule", "NoExecute"):
            taint_reg.set_bit(
                snap.node_taint_bits, i, (taint.key, taint.value, taint.effect)
            )


def task_lane_row(t: TaskInfo, names: List[str], row: np.ndarray) -> bool:
    """Fill one task's resreq lane row (same float op order as the cold
    bulk extraction: f64 memory divide, then f32 downcast on store).
    Returns False when the memory quantity was not MiB-aligned."""
    rr = t.init_resreq
    row[0] = rr.milli_cpu
    row[1] = rr.memory / MIB
    sc = rr.scalars
    if sc and len(names) > 2:
        for r, name in enumerate(names[2:], start=2):
            row[r] = sc.get(name, 0.0)
    return not rr.memory % MIB


def node_lane_rows(
    n: NodeInfo,
    names: List[str],
    idle_row: np.ndarray,
    used_row: np.ndarray,
    alloc_row: np.ndarray,
) -> bool:
    """Fill one node's idle/used/alloc lane rows; returns False when any
    memory quantity was not MiB-aligned."""
    mem_ok = True
    for res, row in ((n.idle, idle_row), (n.used, used_row), (n.allocatable, alloc_row)):
        row[0] = res.milli_cpu
        row[1] = res.memory / MIB
        if res.memory % MIB:
            mem_ok = False
        sc = res.scalars
        if sc and len(names) > 2:
            for r, name in enumerate(names[2:], start=2):
                row[r] = sc.get(name, 0.0)
    return mem_ok


def pack_session(
    tasks: Sequence[TaskInfo],
    jobs: Sequence[JobInfo],
    nodes: Sequence[NodeInfo],
    bit_words: int = DEFAULT_BIT_WORDS,
    pad: bool = True,
    enforce_pod_count: bool = True,
    label_registry: Optional[BitRegistry] = None,
    taint_registry: Optional[BitRegistry] = None,
    spread: bool = True,
) -> PackedSnapshot:
    """Pack pending tasks (in processing order), their jobs and all nodes.

    ``tasks`` must arrive in the order the kernel should consider them —
    the host computes it from the session's task/job order functions, which
    preserves the reference's priority semantics (allocate.go:54-92).

    ``enforce_pod_count`` mirrors whether the predicates plugin is in the
    session's tiers: the pod-number limit lives there (predicates.go:164),
    so without it the host never counts pods and neither should the kernel.

    ``label_registry``/``taint_registry`` seed the bit assignment with a
    persistent registry (ops/pack_cache.py).  Bit indices are append-only,
    so a pack seeded with a registry that already covers the session's
    label/taint pairs produces arrays bit-identical to the pack that
    built the registry — the equivalence contract the warm delta path is
    tested against (tests/test_pack_cache.py).  Note the contract is
    dictionary-level: a warm pack may FIRST-register new pairs in a
    different order than a cold pack would (it packs nodes before tasks
    for relay overlap), so equivalence is defined against a cold pack
    seeded with the resulting registry; bindings are invariant under bit
    permutation either way.

    ``spread=False`` packs for a kernel that models no topology spread
    (see :func:`pack_spread`).
    """
    snap = PackedSnapshot()
    names, tol = _resource_axis(tasks, nodes)
    snap.resource_names = names
    snap.tolerance = tol
    R = len(names)

    T, N, J = len(tasks), len(nodes), len(jobs)
    T_pad = _bucket(T) if pad else max(T, 1)
    N_pad = _bucket(N) if pad else max(N, 1)
    J_pad = _bucket(J, minimum=16) if pad else max(J, 1)

    job_index = {j.uid: i for i, j in enumerate(jobs)}

    label_reg = label_registry if label_registry is not None else BitRegistry(bit_words)
    taint_reg = taint_registry if taint_registry is not None else BitRegistry(bit_words)
    W = label_reg.words

    alloc_planes(snap, R, W, T, N, J, T_pad, N_pad, J_pad)

    # Resource lanes: bulk-extract cpu/memory (the dominant cost at 50k
    # tasks was one tiny np array per task); scalar lanes stay per-task
    # but only exist when the session carries extended resources.
    if T:
        snap.task_resreq[:T, 0] = [t.init_resreq.milli_cpu for t in tasks]
        mem = np.array([t.init_resreq.memory for t in tasks], dtype=np.float64)
        if (mem % MIB).any():
            snap.memory_exact = False
        snap.task_resreq[:T, 1] = mem / MIB
        snap.task_job[:T] = [job_index.get(t.job, 0) for t in tasks]
        if R > 2:
            for i, t in enumerate(tasks):
                sc = t.init_resreq.scalars
                if sc:
                    for r, name in enumerate(names[2:], start=2):
                        snap.task_resreq[i, r] = sc.get(name, 0.0)

    # Tasks: selector/affinity/toleration bits come from the pod spec.
    for i, t in enumerate(tasks):
        snap.task_uids.append(t.uid)
        if pack_task_bits(snap, i, t, label_reg, taint_reg):
            snap.task_needs_host[i] = True
            snap.needs_host_validation = True

    # Nodes: same bulk lane extraction as tasks.
    if N:
        for arr, field_name in (
            (snap.node_idle, "idle"),
            (snap.node_used, "used"),
            (snap.node_alloc, "allocatable"),
        ):
            res_list = [getattr(n, field_name) for n in nodes]
            arr[:N, 0] = [r.milli_cpu for r in res_list]
            mem = np.array([r.memory for r in res_list], dtype=np.float64)
            if (mem % MIB).any():
                snap.memory_exact = False
            arr[:N, 1] = mem / MIB
            if R > 2:
                for i, r in enumerate(res_list):
                    if r.scalars:
                        for k, name in enumerate(names[2:], start=2):
                            arr[i, k] = r.scalars.get(name, 0.0)

    for i, n in enumerate(nodes):
        pack_node_row(snap, i, n, label_reg, taint_reg, enforce_pod_count)
        snap.node_names.append(n.name)

    # Keyed Exists tolerations need the full taint registry, which is only
    # complete after the node pass.
    resolve_exists_tolerations(snap, enumerate(tasks), taint_reg)

    # Jobs.
    for i, j in enumerate(jobs):
        snap.job_min_available[i] = j.min_available
        snap.job_ready_count[i] = j.ready_task_num()
        snap.job_uids.append(j.uid)

    if label_reg.overflow or taint_reg.overflow:
        snap.needs_host_validation = True
        snap.registry_overflow = True

    pack_spread(snap, tasks, nodes, encode=spread)
    return snap


def spread_row_encodable(hard) -> bool:
    """Do the kernels encode these hard constraints exactly?  Not
    ``minDomains`` above 1 (the minimum would depend on the domain
    count), not ``matchLabelKeys``, not an empty or nil selector (which
    counts nothing but still self-matches an empty one), and not a
    maxSkew below 1 (which k8s validation refuses)."""
    for c in hard:
        if c.max_skew < 1:
            return False
        if c.min_domains is not None and c.min_domains > 1:
            return False
        if c.match_label_keys:
            return False
        sel = c.label_selector
        if not sel or not (sel.get("matchLabels") or sel.get("matchExpressions")):
            return False
    return True


def affinity_row_encodable(pod) -> bool:
    """Do the kernels encode this pod's own required inter-pod terms
    exactly?  Its anti-affinity terms, where none carries a namespace
    selector or label keys; not required pod affinity, which no class
    kind expresses yet."""
    from volcano_tpu.plugins import util as putil

    return not putil.required_terms(pod, "podAffinity") and all(
        putil.term_encodable(t) for t in putil.required_terms(pod, "podAntiAffinity")
    )


class _ClassPlanes(NamedTuple):
    """One kind's classes, before :func:`pack_spread` joins the kinds."""

    key_dom: np.ndarray  # [Kk, N_pad] i32
    class_key: np.ndarray  # [K] i32, rows of key_dom
    bound: np.ndarray  # [K] i32
    absolute: np.ndarray  # [K] bool
    elig: np.ndarray  # [K, N_pad] bool
    dom_count: np.ndarray  # [K, D] i32
    on: np.ndarray  # [T_pad, K] bool
    self_: np.ndarray  # [T_pad, K] bool


def pack_spread(snap: "PackedSnapshot", tasks, nodes, encode: bool = True) -> None:
    """Encode the tasks' hard topology spread constraints and required
    pod anti-affinity into ``snap`` (the planes in the module docstring),
    from the pods the session's nodes hold now.  Runs after every pack,
    cold or warm: the planes depend on where pods sit, so none is
    carried between packs.  Spread classes come first; anti-affinity
    classes take what is left of :data:`SPREAD_MAX_CLASSES`.

    A spread row the kernels cannot encode exactly —
    ``spread_row_encodable`` refuses it, its classes overflow, or its
    constraints map to classes out of their order (the explanation's
    first failure follows the pod's order) — is marked in
    ``task_spread_host`` and sets ``needs_host_validation``; so is a row
    whose inter-pod terms are not encoded (:func:`_affinity_classes`).
    ``encode=False`` (the preempt and reclaim packs, whose kernels model
    neither) marks every row with a hard constraint or a required
    inter-pod term so."""
    import contextlib
    import time

    from volcano_tpu import trace
    from volcano_tpu.plugins import util as putil

    T_pad = snap.task_resreq.shape[0]
    N_pad = snap.node_idle.shape[0]
    host = np.zeros(T_pad, dtype=bool)
    parts: List[_ClassPlanes] = []

    def span(name):
        return trace.span(name, "kernel") if encode else contextlib.nullcontext()

    t0 = time.perf_counter()
    class_index: Dict[Tuple, int] = {}
    reps: List[Tuple] = []  # (pod, constraint, class key) of each class
    rows: List[Tuple[int, List[int]]] = []
    device_rows = host_rows = 0
    with span("vtpu/allocate.spread_pack"):
        for i, t in enumerate(tasks):
            pod = t.pod
            if pod is None or not pod.spec.topology_spread_constraints:
                continue
            hard = putil.hard_spread_constraints(pod)
            ids: Optional[List[int]] = None
            if hard and encode and spread_row_encodable(hard):
                ids = []
                for c in hard:
                    key = putil.spread_class_key(pod, c)
                    k = class_index.get(key)
                    if k is None and len(reps) < SPREAD_MAX_CLASSES:
                        k = class_index[key] = len(reps)
                        reps.append((pod, c, key))
                    if k is None or (ids and k < ids[-1]):
                        ids = None
                        break
                    ids.append(k)
            if hard and ids is None:
                host[i] = True
            if ids is not None:
                rows.append((i, ids))
            if ids is not None and not snap.task_has_preferences[i]:
                device_rows += 1
            else:
                host_rows += 1
        if reps:
            parts.append(_spread_planes(tasks, nodes, reps, rows, N_pad, T_pad, putil))
    t1 = time.perf_counter()
    snap.spread_stats = {
        "spread_rows_device": device_rows,
        "spread_rows_host": host_rows,
        "spread_classes": len(reps),
        "spread_pack_ms": (t1 - t0) * 1e3,
    }
    with span("vtpu/allocate.affinity_pack"):
        aff_reps, aff_rows, device_rows, host_rows, holds = _affinity_classes(
            snap, tasks, nodes, host, encode, SPREAD_MAX_CLASSES - len(reps), putil)
        if aff_reps:
            parts.append(_affinity_planes(
                tasks, nodes, aff_reps, aff_rows, holds, N_pad, T_pad, putil))
    snap.affinity_stats = {
        "affinity_rows_device": device_rows,
        "affinity_rows_host": host_rows,
        "affinity_classes": len(aff_reps),
        "affinity_pack_ms": (time.perf_counter() - t1) * 1e3,
    }
    _set_class_planes(snap, parts)
    snap.task_spread_host = host
    if host[: len(tasks)].any():
        snap.needs_host_validation = True


def _set_class_planes(snap, parts: List[_ClassPlanes]) -> None:
    """The planes of every kind's classes, spread's first."""
    if not parts:
        for name in ("spread_key_dom", "spread_class_key", "spread_max_skew",
                     "spread_absolute", "spread_node_elig", "spread_dom_count",
                     "task_spread_on", "task_spread_self"):
            setattr(snap, name, None)
        return
    if len(parts) == 1:
        p = parts[0]
    else:
        width = max(q.dom_count.shape[1] for q in parts)
        offsets = np.cumsum([0] + [q.key_dom.shape[0] for q in parts[:-1]])
        p = _ClassPlanes(
            np.concatenate([q.key_dom for q in parts]),
            np.concatenate([q.class_key + o for q, o in zip(parts, offsets)]).astype(np.int32),
            *(np.concatenate([getattr(q, f) for q in parts])
              for f in ("bound", "absolute", "elig")),
            np.concatenate([
                np.pad(q.dom_count, ((0, 0), (0, width - q.dom_count.shape[1])))
                for q in parts
            ]),
            *(np.concatenate([getattr(q, f) for q in parts], axis=1) for f in ("on", "self_")),
        )
    snap.spread_key_dom = p.key_dom
    snap.spread_class_key = p.class_key
    snap.spread_max_skew = p.bound
    snap.spread_absolute = p.absolute
    snap.spread_node_elig = p.elig
    snap.spread_dom_count = p.dom_count
    snap.task_spread_on = p.on
    snap.task_spread_self = p.self_


def _key_domains(node_objs, keys: List[str], N_pad: int):
    """([len(keys), N_pad] i32 domain index of each node under each key,
    -1 where it lacks the label; the domain count of each key)."""
    key_dom = np.full((len(keys), N_pad), -1, dtype=np.int32)
    n_values = []
    for r, tk in enumerate(keys):
        values = sorted({
            obj.metadata.labels[tk] for obj in node_objs
            if obj is not None and tk in (obj.metadata.labels or {})
        })
        index = {v: d for d, v in enumerate(values)}
        n_values.append(len(values))
        for j, obj in enumerate(node_objs):
            v = (obj.metadata.labels or {}).get(tk) if obj is not None else None
            if v is not None:
                key_dom[r, j] = index[v]
    return key_dom, n_values


def _spread_planes(tasks, nodes, reps, rows, N_pad, T_pad, putil) -> _ClassPlanes:
    """The planes of :func:`pack_spread` for its K registered spread
    classes."""
    K = len(reps)
    node_objs = [n.node for n in nodes]
    keys = list(dict.fromkeys(c.topology_key for _pod, c, _key in reps))
    key_dom, n_values = _key_domains(node_objs, keys, N_pad)
    key_row = {tk: r for r, tk in enumerate(keys)}
    class_key = np.array([key_row[c.topology_key] for _p, c, _k in reps], dtype=np.int32)

    # eligibility depends only on the class key's node-side half
    # (every topology key, affinity and taint signatures)
    elig = np.zeros((K, N_pad), dtype=bool)
    by_sig: Dict[Tuple, np.ndarray] = {}
    for k, (pod, c, key) in enumerate(reps):
        sig = key[4:]
        row = by_sig.get(sig)
        if row is None:
            row = by_sig[sig] = np.array(putil.spread_eligible(pod, c, node_objs), dtype=bool)
        elig[k, : len(node_objs)] = row

    # the classes a pod counts toward depend on its namespace and labels
    # alone: one selector test per (namespace, selector) and label set
    groups: Dict[Tuple, List[int]] = {}
    for k, (pod, c, key) in enumerate(reps):
        groups.setdefault(key[:2], []).append(k)
    group_list = [
        (ks, reps[ks[0]][0].metadata.namespace,
         putil.spread_selector(reps[ks[0]][0], reps[ks[0]][1]))
        for ks in groups.values()
    ]
    memo: Dict[Tuple, List[int]] = {}

    def matches(pod) -> List[int]:
        labels = pod.metadata.labels or {}
        mkey = (pod.metadata.namespace, pod.metadata.deletion_timestamp is None,
                tuple(labels.items()))
        hit = memo.get(mkey)
        if hit is None:
            hit = memo[mkey] = [
                k for ks, ns, sel in group_list
                if putil.spread_counts_pod(sel, ns, pod) for k in ks
            ]
        return hit

    dom_count = np.zeros((K, max(max(n_values), 1)), dtype=np.int32)
    dom_of = key_dom[class_key].tolist()  # [K][N_pad]
    elig_of = elig.tolist()
    for j, n in enumerate(nodes):
        for _uid, pod in putil.counted_pods(n):
            for k in matches(pod):
                if elig_of[k][j]:
                    dom_count[k, dom_of[k][j]] += 1

    on = np.zeros((T_pad, K), dtype=bool)
    for i, ids in rows:
        on[i, ids] = True
    self_ = np.zeros((T_pad, K), dtype=bool)
    for i, t in enumerate(tasks):
        if t.pod is not None:
            self_[i, matches(t.pod)] = True

    bound = np.array([int(c.max_skew) for _p, c, _k in reps], dtype=np.int32)
    return _ClassPlanes(key_dom, class_key, bound, np.zeros(K, dtype=bool), elig,
                        dom_count, on, self_)


def _affinity_classes(snap, tasks, nodes, host, encode: bool, budget: int, putil):
    """The anti-affinity classes of the rows, at most ``budget`` of them:
    (reps, rows, device rows, host rows, the term keys each pod holds by
    uid), where each rep is ``(class key,
    selector)`` — the key ``("match", namespaces, selector, topology
    key)`` for a row's own term, ``("hold", ...)`` for a term a placed or
    pending pod holds and the row matches.  A row whose terms the
    kernels cannot encode (:func:`affinity_row_encodable`, a held term
    with a namespace selector, the budget) is marked in ``host``.  Rows
    are counted as affinity rows where they hold a required term or
    match a held one."""
    held: Dict[Tuple, list] = {}  # term key -> [selector, encodable]
    holds: Dict[str, List[Tuple]] = {}  # uid -> keys of the terms its pod holds

    def hold(t) -> None:
        keys = holds[t.uid] = []
        for key, term in putil.held_anti_affinity(t.pod):
            keys.append(key)
            entry = held.setdefault(key, [term.get("labelSelector"), True])
            entry[1] = entry[1] and putil.term_encodable(term)

    if encode:
        for n in nodes:
            for t in n.tasks.values():
                if t.pod is not None and t.pod.spec.affinity:
                    hold(t)
        for t in tasks:
            if t.pod is not None and t.pod.spec.affinity:
                hold(t)
    memo: Dict[Tuple, List[Tuple]] = {}

    def matched_held(pod) -> List[Tuple]:
        mkey = (pod.metadata.namespace, tuple((pod.metadata.labels or {}).items()))
        hit = memo.get(mkey)
        if hit is None:
            hit = memo[mkey] = [
                key for key, (sel, _ok) in held.items()
                if putil.pod_matches_term(key[0], sel, pod)
            ]
        return hit

    class_index: Dict[Tuple, int] = {}
    reps: List[Tuple] = []
    rows: List[Tuple[int, List[int]]] = []
    device_rows = host_rows = 0
    for i, t in enumerate(tasks):
        pod = t.pod
        if pod is None:
            continue
        own = putil.required_terms(pod, "podAntiAffinity") if pod.spec.affinity else []
        sym = matched_held(pod) if held else []
        if not own and not sym and not (
            pod.spec.affinity and putil.required_terms(pod, "podAffinity")
        ):
            continue
        ids: Optional[List[int]] = None
        if encode and affinity_row_encodable(pod) and all(held[k][1] for k in sym):
            ids = []
            # a pod's own anti-affinity terms are the terms it holds
            wanted = [(("match",) + key, held[key][0]) for key in holds.get(t.uid, ())] + [
                (("hold",) + key, held[key][0]) for key in sym]
            for ckey, sel in wanted:
                k = class_index.get(ckey)
                if k is None and len(reps) < budget:
                    k = class_index[ckey] = len(reps)
                    reps.append((ckey, sel))
                if k is None:
                    ids = None
                    break
                ids.append(k)
        if ids is None:
            host[i] = True
        else:
            rows.append((i, ids))
        if ids is not None and not snap.task_has_preferences[i]:
            device_rows += 1
        else:
            host_rows += 1
    return reps, rows, device_rows, host_rows, holds


def _affinity_planes(tasks, nodes, reps, rows, holds, N_pad, T_pad, putil) -> _ClassPlanes:
    """The planes of the anti-affinity classes: a node is a domain of a
    class where it carries the key, every pod its node holds counts
    (whatever its status, as the host's counts have it), and the bound
    is 0."""
    K = len(reps)
    node_objs = [n.node for n in nodes]
    keys = list(dict.fromkeys(ckey[-1] for ckey, _sel in reps))
    key_dom, n_values = _key_domains(node_objs, keys, N_pad)
    key_row = {tk: r for r, tk in enumerate(keys)}
    class_key = np.array([key_row[ckey[-1]] for ckey, _sel in reps], dtype=np.int32)
    dom = key_dom[class_key]  # [K, N_pad]

    # a pod counts toward a "match" class by its namespace and labels,
    # toward a "hold" class by the terms it holds
    match = [(k, ckey[1], sel) for k, (ckey, sel) in enumerate(reps) if ckey[0] == "match"]
    hold_class = {ckey[1:]: k for k, (ckey, _sel) in enumerate(reps) if ckey[0] == "hold"}
    memo: Dict[Tuple, List[int]] = {}

    def counts_toward(t) -> List[int]:
        pod = t.pod
        mkey = (pod.metadata.namespace, tuple((pod.metadata.labels or {}).items()))
        hit = memo.get(mkey)
        if hit is None:
            hit = memo[mkey] = [
                k for k, ns, sel in match if putil.pod_matches_term(ns, sel, pod)
            ]
        held = holds.get(t.uid)
        if held and hold_class:
            hit = hit + [hold_class[key] for key in held if key in hold_class]
        return hit

    dom_count = np.zeros((K, max(max(n_values), 1)), dtype=np.int32)
    dom_of = dom.tolist()
    for j, n in enumerate(nodes):
        for t in n.tasks.values():
            if t.pod is not None:
                for k in counts_toward(t):
                    if dom_of[k][j] >= 0:
                        dom_count[k, dom_of[k][j]] += 1

    on = np.zeros((T_pad, K), dtype=bool)
    for i, ids in rows:
        on[i, ids] = True
    self_ = np.zeros((T_pad, K), dtype=bool)
    for i, t in enumerate(tasks):
        if t.pod is not None:
            self_[i, counts_toward(t)] = True
    return _ClassPlanes(key_dom, class_key, np.zeros(K, dtype=np.int32),
                        np.ones(K, dtype=bool), dom >= 0, dom_count, on, self_)


def spread_node_planes(snap: "PackedSnapshot", width: int):
    """(dom, elig, count) [K, width] f32 node planes of the kernels:
    each node's domain under its class's key (-1 without the label),
    whether it counts toward the class, and the matching pods of its
    domain when packed (0 without the label)."""
    K = snap.spread_max_skew.shape[0]
    n = min(width, snap.spread_key_dom.shape[1])
    dom = np.full((K, width), -1.0, dtype=np.float32)
    elig = np.zeros((K, width), dtype=np.float32)
    count = np.zeros((K, width), dtype=np.float32)
    d = snap.spread_key_dom[snap.spread_class_key][:, :n]
    dom[:, :n] = d
    elig[:, :n] = snap.spread_node_elig[:, :n]
    got = np.take_along_axis(snap.spread_dom_count, np.maximum(d, 0), axis=1)
    count[:, :n] = np.where(d >= 0, got, 0)
    return dom, elig, count


def class_absolute(snap: "PackedSnapshot") -> np.ndarray:
    """[K] bool: the classes of the absolute kind (all False for a
    snapshot packed before the kind existed)."""
    absolute = getattr(snap, "spread_absolute", None)
    if absolute is None:
        return np.zeros(snap.spread_max_skew.shape[0], dtype=bool)
    return absolute.astype(bool)


def spread_task_columns(snap: "PackedSnapshot", rows: int):
    """(thr, inc) [rows, K] f32 task columns of the kernels: ``thr`` is
    the most a node's domain count may exceed the minimum by — maxSkew
    less the row's self-match — or, for a class of the absolute kind,
    the most it may hold (the bound), or -1 where the class does not
    constrain the row; ``inc`` is 1 where the row counts toward the
    class once placed."""
    K = snap.spread_max_skew.shape[0]
    n = min(rows, snap.task_spread_on.shape[0])
    thr = np.full((rows, K), -1.0, dtype=np.float32)
    inc = np.zeros((rows, K), dtype=np.float32)
    on = snap.task_spread_on[:n]
    self_ = snap.task_spread_self[:n].astype(np.float32)
    bound = snap.spread_max_skew[None, :] - np.where(class_absolute(snap)[None, :], 0.0, self_)
    thr[:n] = np.where(on, bound, -1.0)
    inc[:n] = self_
    return thr, inc
