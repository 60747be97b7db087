"""Shared predicate/score helpers: label selectors, affinity terms, taints,
topology spread.

Reference: pkg/scheduler/plugins/util/util.go (listers) and the used subset
of the vendored k8s predicate algorithms
(vendor/k8s.io/kubernetes/pkg/scheduler/algorithm/predicates) re-expressed
natively — these are the exact semantics the device kernels encode as
bitmask lanes (volcano_tpu.ops.predicates).
"""

from __future__ import annotations

from typing import Dict, Iterable, List, Optional, Tuple

from volcano_tpu.api import NodeInfo, TaskInfo, TaskStatus
from volcano_tpu.api import unschedule_info as reasons
from volcano_tpu.apis import core

DEFAULT_TOPOLOGY_KEY = "kubernetes.io/hostname"


# ---- label selector (k8s metav1.LabelSelector semantics) ----

def match_expressions(labels: Dict[str, str], exprs: Iterable[dict]) -> bool:
    for e in exprs or []:
        key = e.get("key", "")
        op = e.get("operator", "In")
        values = e.get("values", []) or []
        have = key in labels
        val = labels.get(key)
        if op == "In":
            if not have or val not in values:
                return False
        elif op == "NotIn":
            if have and val in values:
                return False
        elif op == "Exists":
            if not have:
                return False
        elif op == "DoesNotExist":
            if have:
                return False
        elif op == "Gt":
            if not have or not values or not _int_cmp(val, values[0], greater=True):
                return False
        elif op == "Lt":
            if not have or not values or not _int_cmp(val, values[0], greater=False):
                return False
        else:
            return False
    return True


def _int_cmp(val: Optional[str], bound: str, greater: bool) -> bool:
    try:
        v, b = int(str(val)), int(str(bound))
    except (TypeError, ValueError):
        return False
    return v > b if greater else v < b


def match_label_selector(labels: Dict[str, str], selector: Optional[dict]) -> bool:
    """k8s LabelSelectorAsSelector semantics: empty selector matches all."""
    if not selector:
        return True
    for k, v in (selector.get("matchLabels") or {}).items():
        if labels.get(k) != v:
            return False
    return match_expressions(labels, selector.get("matchExpressions"))


# ---- node selector / node affinity ----

def pod_matches_node_selector(pod: core.Pod, node: core.Node) -> bool:
    """vendored predicates.PodMatchNodeSelector: nodeSelector AND required
    node affinity must both hold."""
    for k, v in (pod.spec.node_selector or {}).items():
        if node.metadata.labels.get(k) != v:
            return False

    node_affinity = (pod.spec.affinity or {}).get("nodeAffinity") or {}
    required = node_affinity.get("requiredDuringSchedulingIgnoredDuringExecution")
    if required:
        terms = required.get("nodeSelectorTerms") or []
        # OR over terms, AND within a term.
        if terms and not any(
            match_expressions(node.metadata.labels, t.get("matchExpressions"))
            and _match_fields(node, t.get("matchFields"))
            for t in terms
        ):
            return False
    return True


def _match_fields(node: core.Node, field_exprs: Optional[List[dict]]) -> bool:
    """Only metadata.name is a valid field selector in k8s."""
    for e in field_exprs or []:
        if e.get("key") == "metadata.name":
            values = e.get("values", []) or []
            op = e.get("operator", "In")
            if op == "In" and node.metadata.name not in values:
                return False
            if op == "NotIn" and node.metadata.name in values:
                return False
    return True


def node_affinity_score(pod: core.Pod, node: core.Node) -> int:
    """vendored priorities.CalculateNodeAffinityPriorityMap: sum of weights
    of matching preferred terms (normalized to 0-10 by the caller when the
    max is known; the reference applies no per-node normalization in
    nodeorder, so raw weight sum capped at MaxPriority semantics are applied
    at reduce time — here we return the raw sum like the map phase does)."""
    node_affinity = (pod.spec.affinity or {}).get("nodeAffinity") or {}
    preferred = (
        node_affinity.get("preferredDuringSchedulingIgnoredDuringExecution") or []
    )
    count = 0
    for p in preferred:
        weight = int(p.get("weight", 0))
        term = p.get("preference") or {}
        if weight == 0:
            continue
        if match_expressions(node.metadata.labels, term.get("matchExpressions")):
            count += weight
    return count


# ---- taints / tolerations ----

def toleration_tolerates_taint(tol: core.Toleration, taint: core.Taint) -> bool:
    if tol.effect and tol.effect != taint.effect:
        return False
    if tol.key and tol.key != taint.key:
        return False
    # empty key with Exists matches all taints
    if tol.operator == "Exists":
        return True
    return tol.value == taint.value


def pod_tolerates_node_taints(pod: core.Pod, node: core.Node) -> bool:
    """vendored predicates.PodToleratesNodeTaints — only NoSchedule/NoExecute
    taints are scheduling-relevant."""
    for taint in node.spec.taints or []:
        if taint.effect not in ("NoSchedule", "NoExecute"):
            continue
        if not any(
            toleration_tolerates_taint(t, taint) for t in pod.spec.tolerations or []
        ):
            return False
    return True


# ---- host ports ----

def pod_host_ports(pod: core.Pod) -> List[tuple]:
    out = []
    for c in pod.spec.containers:
        for p in c.ports or []:
            if p.host_port:
                out.append((p.protocol or "TCP", p.host_port))
    return out


def fits_host_ports(pod: core.Pod, existing_pods: Iterable[core.Pod]) -> bool:
    wanted = set(pod_host_ports(pod))
    if not wanted:
        return True
    used = set()
    for ep in existing_pods:
        used.update(pod_host_ports(ep))
    return not (wanted & used)


# ---- pod (anti-)affinity ----

def required_terms(pod: core.Pod, kind: str) -> List[dict]:
    aff = (pod.spec.affinity or {}).get(kind) or {}
    return aff.get("requiredDuringSchedulingIgnoredDuringExecution") or []


def _preferred_terms(pod: core.Pod, kind: str) -> List[dict]:
    aff = (pod.spec.affinity or {}).get(kind) or {}
    return aff.get("preferredDuringSchedulingIgnoredDuringExecution") or []


def _term_matches_pod(term: dict, pod: core.Pod, candidate: core.Pod) -> bool:
    """Does `candidate` (an existing pod) match the term from `pod`'s view?
    Namespace semantics: empty namespaces list = the affinity pod's own
    namespace."""
    namespaces = term.get("namespaces") or [pod.metadata.namespace]
    if candidate.metadata.namespace not in namespaces:
        return False
    return match_label_selector(
        candidate.metadata.labels, term.get("labelSelector")
    )


def _same_topology(
    node_a: Optional[core.Node], node_b: Optional[core.Node], topology_key: str
) -> bool:
    if node_a is None or node_b is None:
        return False
    key = topology_key or DEFAULT_TOPOLOGY_KEY
    va = node_a.metadata.labels.get(key)
    vb = node_b.metadata.labels.get(key)
    return va is not None and va == vb


# ---- pod topology spread (k8s v1.30 plugins/podtopologyspread) ----

HARD_SPREAD = "DoNotSchedule"
SOFT_SPREAD = "ScheduleAnyway"


def hard_spread_constraints(pod: core.Pod) -> List[core.TopologySpreadConstraint]:
    """The constraints the Filter enforces: ``whenUnsatisfiable:
    DoNotSchedule``, in the pod's order."""
    return [
        c for c in pod.spec.topology_spread_constraints or []
        if c.when_unsatisfiable == HARD_SPREAD
    ]


def has_soft_spread(pod: core.Pod) -> bool:
    """``ScheduleAnyway`` constraints are scoring, not filtering."""
    return any(
        c.when_unsatisfiable == SOFT_SPREAD
        for c in pod.spec.topology_spread_constraints or []
    )


def spread_selector(pod: core.Pod, c: core.TopologySpreadConstraint) -> Optional[dict]:
    """The constraint's selector with ``matchLabelKeys`` merged in: one
    ``In`` requirement per key the incoming pod carries, at its value
    (filterTopologySpreadConstraints).  None is k8s's nil selector,
    which matches nothing."""
    sel = c.label_selector
    if sel is None or not c.match_label_keys:
        return sel
    labels = pod.metadata.labels or {}
    extra = [
        {"key": k, "operator": "In", "values": [labels[k]]}
        for k in c.match_label_keys if k in labels
    ]
    if not extra:
        return sel
    return {**sel, "matchExpressions": list(sel.get("matchExpressions") or []) + extra}


def spread_self_match(sel: Optional[dict], pod: core.Pod) -> int:
    """The Filter's self-match term: 1 when the selector matches the
    incoming pod's own labels.  A nil selector matches nothing, an
    empty one everything."""
    if sel is None:
        return 0
    return int(match_label_selector(pod.metadata.labels or {}, sel))


def spread_counts_pod(sel: Optional[dict], namespace: str, pod: Optional[core.Pod]) -> bool:
    """countPodsMatchSelector for one pod: an empty (or nil) selector
    counts nothing; a pod counts when it lives in the incoming pod's
    namespace, is not terminating, and matches."""
    if pod is None or not sel or not (sel.get("matchLabels") or sel.get("matchExpressions")):
        return False
    if pod.metadata.namespace != namespace or pod.metadata.deletion_timestamp is not None:
        return False
    return match_label_selector(pod.metadata.labels or {}, sel)


def _selector_key(sel: Optional[dict]):
    """A hashable form of a label selector (None stays None)."""
    if sel is None:
        return None
    return (
        tuple(sorted((sel.get("matchLabels") or {}).items())),
        tuple((e.get("key"), e.get("operator", "In"), tuple(e.get("values") or ()))
              for e in sel.get("matchExpressions") or ()),
    )


def _freeze(obj):
    """A hashable copy of a dict/list tree, dict keys sorted (node affinity)."""
    if isinstance(obj, dict):
        return tuple(sorted((k, _freeze(v)) for k, v in obj.items()))
    if isinstance(obj, (list, tuple)):
        return tuple(_freeze(v) for v in obj)
    return obj


def spread_class_key(
    pod: core.Pod, c: core.TopologySpreadConstraint
) -> Tuple:
    """What two hard constraints must share to share their per-domain
    counts and their verdict on every node: the namespace and selector
    that count pods, the topology key, maxSkew, and what makes a node
    eligible to count — every topology key of the pod's hard
    constraints, and the pod's node selector and required affinity or
    tolerations where the constraint's inclusion policies honour them."""
    keys = tuple(sorted({h.topology_key for h in hard_spread_constraints(pod)}))
    affinity = taints = None
    if (c.node_affinity_policy or "Honor") == "Honor" and (
        pod.spec.node_selector or pod.spec.affinity
    ):
        affinity = _freeze([
            pod.spec.node_selector or {},
            ((pod.spec.affinity or {}).get("nodeAffinity") or {}).get(
                "requiredDuringSchedulingIgnoredDuringExecution"),
        ])
    if (c.node_taints_policy or "Ignore") == "Honor":
        taints = tuple((t.key, t.operator, t.value, t.effect) for t in pod.spec.tolerations or [])
    return (
        pod.metadata.namespace, _selector_key(spread_selector(pod, c)), c.topology_key,
        int(c.max_skew), keys, affinity, taints,
    )


def spread_eligible(
    pod: core.Pod, c: core.TopologySpreadConstraint, nodes: List[Optional[core.Node]]
) -> List[bool]:
    """Which of ``nodes`` count toward the constraint's domains
    (calPreFilterState)?  A node must carry every topology key of the
    pod's hard constraints and pass the inclusion policies: the pod's
    node selector and required affinity under ``nodeAffinityPolicy:
    Honor`` (the default), its tolerations under ``nodeTaintsPolicy:
    Honor``."""
    keys = {h.topology_key for h in hard_spread_constraints(pod)}
    affinity = (c.node_affinity_policy or "Honor") == "Honor" and bool(
        pod.spec.node_selector or pod.spec.affinity
    )
    taints = (c.node_taints_policy or "Ignore") == "Honor"
    return [
        node is not None
        and keys.issubset(node.metadata.labels or {})
        and not (affinity and not pod_matches_node_selector(pod, node))
        and not (taints and not pod_tolerates_node_taints(pod, node))
        for node in nodes
    ]


def counted_pods(node: NodeInfo):
    """The pods on a session node that spread counting sees: every task
    the node holds except those being released (a Releasing task is
    a pod on its way out, which k8s skips by its deletion timestamp)."""
    for t in node.tasks.values():
        if t.pod is not None and t.status != TaskStatus.Releasing:
            yield t.uid, t.pod


class SpreadClass:
    """One (namespace, selector, topology key, maxSkew, eligibility)
    class: the matching pods per eligible domain, and their minimum,
    kept in O(1) per update by a histogram of the domain counts."""

    __slots__ = ("namespace", "selector", "key", "domain_of", "counts", "_hist", "_min")

    def __init__(self, namespace: str, selector: Optional[dict], key: str,
                 domain_of: Dict[str, str]):
        self.namespace = namespace
        self.selector = selector
        self.key = key
        #: eligible node name → its domain (the node's value of ``key``)
        self.domain_of = domain_of
        self.counts: Dict[str, int] = dict.fromkeys(domain_of.values(), 0)
        self._hist: Dict[int, int] = {0: len(self.counts)} if self.counts else {}
        self._min = 0

    def _move(self, domain: str, step: int) -> None:
        v = self.counts[domain]
        self.counts[domain] = v + step
        self._hist[v] -= 1
        self._hist[v + step] = self._hist.get(v + step, 0) + 1
        if step < 0 and v + step < self._min:
            self._min = v + step
        elif v == self._min and not self._hist[v]:
            self._min = v + 1

    def add(self, pod: core.Pod, node_name: str, step: int = 1) -> None:
        domain = self.domain_of.get(node_name)
        if domain is not None and spread_counts_pod(self.selector, self.namespace, pod):
            self._move(domain, step)

    def min_match(self, min_domains: Optional[int]) -> float:
        """The global minimum over eligible domains; 0 while fewer
        domains than ``minDomains`` exist, unbounded with none."""
        if not self.counts:
            return float("inf")
        if min_domains is not None and len(self.counts) < min_domains:
            return 0
        return self._min


class TopologySpread:
    """Per-session PreFilter state of PodTopologySpread: one
    :class:`SpreadClass` per distinct hard constraint the session has
    checked, built on its first check from the session's nodes and kept
    in step by the allocate/deallocate events, so a (task, node) check
    costs O(constraints) rather than a scan of every pod."""

    def __init__(self, session):
        self.session = session
        self._classes: Dict[Tuple, SpreadClass] = {}
        #: task uid → [(constraint, class, self-match)] of its hard constraints
        self._task_classes: Dict[str, list] = {}
        #: uid → (node name, pod) of every counted pod; None until a class exists
        self._where: Optional[Dict[str, Tuple[str, core.Pod]]] = None

    @property
    def active(self) -> bool:
        return self._where is not None

    def update(self, task: TaskInfo, node_name: str) -> None:
        """``task`` now sits on ``node_name`` ("" when it left its node
        or is being released)."""
        if self._where is None:
            return
        old = self._where.get(task.uid)
        if old is not None and old[0] == node_name:
            return
        if old is not None:
            del self._where[task.uid]
            for cls in self._classes.values():
                cls.add(old[1], old[0], -1)
        if node_name and task.pod is not None:
            self._where[task.uid] = (node_name, task.pod)
            for cls in self._classes.values():
                cls.add(task.pod, node_name)

    def _class(self, pod: core.Pod, c: core.TopologySpreadConstraint) -> SpreadClass:
        key = spread_class_key(pod, c)
        cls = self._classes.get(key)
        if cls is not None:
            return cls
        if self._where is None:
            self._where = {
                uid: (node.name, p)
                for node in self.session.nodes.values()
                for uid, p in counted_pods(node)
            }
        nodes = list(self.session.nodes.values())
        domain_of = {
            node.name: node.node.metadata.labels[c.topology_key]
            for node, ok in zip(nodes, spread_eligible(pod, c, [n.node for n in nodes]))
            if ok
        }
        cls = SpreadClass(pod.metadata.namespace, spread_selector(pod, c),
                          c.topology_key, domain_of)
        for node_name, p in self._where.values():
            cls.add(p, node_name)
        self._classes[key] = cls
        return cls

    def filter(self, task: TaskInfo, node: NodeInfo) -> Optional[str]:
        """None when ``task`` may land on ``node`` under every hard
        constraint, else the Filter's reason (PodTopologySpread.Filter):
        a node without a constraint's key fails, and otherwise the skew
        — matching pods in the node's domain, plus one when the pod
        matches its own selector, less the global minimum — may not
        pass maxSkew."""
        entries = self._task_classes.get(task.uid)
        if entries is None:
            pod = task.pod
            entries = [
                (c, self._class(pod, c), spread_self_match(spread_selector(pod, c), pod))
                for c in hard_spread_constraints(pod)
            ]
            self._task_classes[task.uid] = entries
        labels = node.node.metadata.labels if node.node is not None else {}
        for c, cls, self_match in entries:
            domain = labels.get(c.topology_key)
            if domain is None:
                return reasons.SPREAD_LABEL_MISSING
            skew = cls.counts.get(domain, 0) + self_match - cls.min_match(c.min_domains)
            if skew > c.max_skew:
                return reasons.SPREAD_CONSTRAINTS_MISMATCH
        return None


# ---- inter-pod affinity (k8s v1.30 plugins/interpodaffinity) ----

def term_key(term: dict, pod: core.Pod) -> Tuple:
    """What two terms must share to count the same pods in the same
    domains: the namespaces (the term's list, or the pod's own namespace
    when it is empty; ``namespaceSelector`` is not honoured, and a term
    carrying one stays off the device), the selector and the topology
    key."""
    return (
        tuple(sorted(term.get("namespaces") or [pod.metadata.namespace])),
        _selector_key(term.get("labelSelector")),
        term.get("topologyKey") or DEFAULT_TOPOLOGY_KEY,
    )


def term_encodable(term: dict) -> bool:
    """Is a term within what the device encodes: no ``namespaceSelector``
    and no ``matchLabelKeys`` / ``mismatchLabelKeys``?"""
    return not (
        term.get("namespaceSelector") is not None
        or term.get("matchLabelKeys") or term.get("mismatchLabelKeys")
    )


def pod_matches_term(namespaces: Tuple[str, ...], selector: Optional[dict],
                     pod: core.Pod) -> bool:
    """Does ``pod`` fall under a term of these namespaces and selector?"""
    return pod.metadata.namespace in namespaces and match_label_selector(
        pod.metadata.labels or {}, selector
    )


def held_anti_affinity(pod: Optional[core.Pod]) -> List[Tuple[Tuple, dict]]:
    """(term key, term) of each required anti-affinity term ``pod`` holds."""
    if pod is None or not pod.spec.affinity:
        return []
    return [(term_key(t, pod), t) for t in required_terms(pod, "podAntiAffinity")]


class TermCounts:
    """Pods per domain of one topology key: those that match a term
    (``matched``), or those that hold a required anti-affinity term
    (``held``).  Only nodes that carry the key are domains."""

    __slots__ = ("namespaces", "selector", "key", "counts", "total")

    def __init__(self, namespaces: Tuple[str, ...], selector: Optional[dict], key: str):
        self.namespaces = namespaces
        self.selector = selector
        self.key = key
        self.counts: Dict[str, int] = {}
        self.total = 0

    def move(self, domain: Optional[str], step: int) -> None:
        if domain is not None:
            self.counts[domain] = self.counts.get(domain, 0) + step
            self.total += step


class _PodTerms:
    """One incoming pod's view of the counts: (class, whether the pod
    itself counts toward it) for its own required affinity terms, its
    own required anti-affinity terms, and the held classes whose term
    matches it (refreshed when a new held class appears)."""

    __slots__ = ("affinity", "anti", "sym", "sym_rev", "self_affine")

    def __init__(self, affinity, anti, self_affine: bool):
        self.affinity: List[Tuple[TermCounts, bool]] = affinity
        self.anti: List[Tuple[TermCounts, bool]] = anti
        self.sym: List[Tuple[TermCounts, bool]] = []
        self.sym_rev = -1
        #: the pod matches every one of its own affinity terms
        self.self_affine = self_affine


class InterPodAffinity:
    """Per-session PreFilter state of InterPodAffinity's required terms:
    per distinct (namespaces, selector, topology key), the pods in each
    domain that match the term, and the pods in each domain that hold it
    as a required anti-affinity term.  Built on the first check from the
    pods the session's nodes hold, kept in step by the allocate and
    deallocate events, so a (task, node) check costs O(terms) rather
    than a scan of every pod.

    A pod counts while its node holds it, whatever its status: an
    evicted (Releasing) pod still excludes others from its domain until
    it is gone, as a terminating pod does in kube-scheduler."""

    def __init__(self, session):
        self.session = session
        #: uid → (node name, pod) of every pod the session's nodes hold
        self._where: Optional[Dict[str, Tuple[str, core.Pod]]] = None
        self._matched: Dict[Tuple, TermCounts] = {}
        self._held: Dict[Tuple, TermCounts] = {}
        self._held_rev = 0
        self._pods: Dict[str, _PodTerms] = {}
        #: whether a pod the nodes hold carries required anti-affinity;
        #: None until asked, kept true by the events once it is
        self._holders: Optional[bool] = None

    @property
    def tracking(self) -> bool:
        """Does the state follow placements (built, or holders known)?"""
        return self._where is not None or self._holders is not None

    def _labels(self, node_name: str) -> Dict[str, str]:
        node = self.session.nodes.get(node_name)
        if node is None or node.node is None:
            return {}
        return node.node.metadata.labels or {}

    def any_held(self) -> bool:
        """Does any pod the session's nodes hold carry a required
        anti-affinity term?  One scan of the nodes on the first ask,
        until the counts exist."""
        if self._where is not None:
            return any(c.total > 0 for c in self._held.values())
        if self._holders is None:
            self._holders = any(
                t.pod is not None and bool(held_anti_affinity(t.pod))
                for node in self.session.nodes.values()
                for t in node.tasks.values()
            )
        return self._holders

    def _build(self) -> None:
        self._where = {}
        for node in self.session.nodes.values():
            for t in node.tasks.values():
                if t.pod is not None:
                    self._where[t.uid] = (node.name, t.pod)
                    self._count(t.pod, node.name, 1)

    def _count(self, pod: core.Pod, node_name: str, step: int) -> None:
        labels = self._labels(node_name)
        for cls in self._matched.values():
            if pod_matches_term(cls.namespaces, cls.selector, pod):
                cls.move(labels.get(cls.key), step)
        for key, term in held_anti_affinity(pod):
            cls = self._held.get(key)
            if cls is None:
                # no placed pod held it before, so its counts start at 0
                cls = self._held[key] = TermCounts(
                    key[0], term.get("labelSelector"), key[2])
                self._held_rev += 1
            cls.move(labels.get(cls.key), step)

    def update(self, task: TaskInfo) -> None:
        """Follow ``task`` to where the session's nodes now hold it."""
        if not self.tracking:
            return
        node = self.session.nodes.get(task.node_name) if task.node_name else None
        here = node.name if node is not None and task.uid in node.tasks else ""
        if self._where is None:
            if here and held_anti_affinity(task.pod):
                self._holders = True
            return
        old = self._where.get(task.uid)
        if old is not None and old[0] == here:
            return
        if old is not None:
            del self._where[task.uid]
            self._count(old[1], old[0], -1)
        if here and task.pod is not None:
            self._where[task.uid] = (here, task.pod)
            self._count(task.pod, here, 1)

    def _matched_class(self, term: dict, pod: core.Pod) -> TermCounts:
        key = term_key(term, pod)
        cls = self._matched.get(key)
        if cls is None:
            cls = self._matched[key] = TermCounts(key[0], term.get("labelSelector"), key[2])
            for node_name, p in self._where.values():
                if pod_matches_term(cls.namespaces, cls.selector, p):
                    cls.move(self._labels(node_name).get(cls.key), 1)
        return cls

    def _terms(self, task: TaskInfo) -> _PodTerms:
        pod = task.pod
        entry = self._pods.get(task.uid)
        if entry is None:
            def own(kind):
                out = []
                for t in required_terms(pod, kind):
                    cls = self._matched_class(t, pod)
                    out.append((cls, pod_matches_term(cls.namespaces, cls.selector, pod)))
                return out

            aff = own("podAffinity")
            entry = self._pods[task.uid] = _PodTerms(
                aff, own("podAntiAffinity"), all(s for _c, s in aff))
        if entry.sym_rev != self._held_rev:
            holds = {k for k, _t in held_anti_affinity(pod)}
            entry.sym = [
                (c, key in holds) for key, c in self._held.items()
                if pod_matches_term(c.namespaces, c.selector, pod)
            ]
            entry.sym_rev = self._held_rev
        return entry

    def filter(self, task: TaskInfo, node: NodeInfo) -> Optional[str]:
        """None when ``task`` may land on ``node`` under every required
        inter-pod term, else the Filter's reason.  Three rules, each
        against the counts of the node's domain with the pod itself left
        out: each own affinity term needs a matching pod there (or, for
        the first pod of a series, no matching pod anywhere and the pod
        matching its own terms), no own anti-affinity term may find one,
        and no pod there may hold a term the pod matches (symmetry).  A
        node without a term's key is no domain of it: affinity fails
        there, anti-affinity does not."""
        if self._where is None:
            self._build()
        entry = self._terms(task)
        labels = node.node.metadata.labels if node.node is not None else {}
        own = self._where.get(task.uid)
        own_labels = self._labels(own[0]) if own is not None else {}

        def others(cls: TermCounts, counts_self: bool, domain: str) -> int:
            """Pods of ``domain`` in the counts, less the pod itself."""
            return cls.counts.get(domain, 0) - int(
                counts_self and own_labels.get(cls.key) == domain)

        if entry.affinity:
            found = True
            for cls, counts_self in entry.affinity:
                domain = labels.get(cls.key)
                if domain is None:
                    return reasons.POD_AFFINITY_MISMATCH
                if others(cls, counts_self, domain) <= 0:
                    found = False
            # the first of a series: self_affine means the pod counts
            # toward every one of these classes where its node has the key
            if not found and not (entry.self_affine and all(
                cls.total - int(cls.key in own_labels) == 0 for cls, _s in entry.affinity
            )):
                return reasons.POD_AFFINITY_MISMATCH
        for rule in (entry.anti, entry.sym):
            for cls, counts_self in rule:
                domain = labels.get(cls.key)
                if domain is not None and others(cls, counts_self, domain) > 0:
                    return reasons.POD_AFFINITY_MISMATCH
        return None


class PodLister:
    """Session-wide pod view for relational predicates.

    Reference: plugins/util/util.go PodLister — presents session tasks as
    pods with up-to-date NodeName as allocations mutate mid-session.
    """

    def __init__(self, session):
        self.session = session
        # task uid -> (pod, node_name); node objects resolved via session.
        self._task_nodes: Dict[str, str] = {}
        #: hard topology spread counts, kept in step with the same events
        self.spread = TopologySpread(session)
        #: required inter-pod (anti-)affinity counts, likewise
        self.affinity = InterPodAffinity(session)
        for job in session.jobs.values():
            for task in job.tasks.values():
                if task.pod is not None:
                    self._task_nodes[task.uid] = task.node_name

    def update_task(self, task: TaskInfo, node_name: str) -> None:
        self._task_nodes[task.uid] = node_name
        self.spread.update(task, node_name)
        self.affinity.update(task)

    def any_required_anti_affinity(self) -> bool:
        """True iff a pod the session's nodes hold declares required
        anti-affinity."""
        return self.affinity.any_held()

    def pods_on_node(self, node: NodeInfo) -> List[core.Pod]:
        return [t.pod for t in node.tasks.values() if t.pod is not None]

    def assigned_pods(self) -> List[tuple]:
        """[(pod, node_name)] for every assigned task in the session."""
        out = []
        for job in self.session.jobs.values():
            for task in job.tasks.values():
                if task.pod is None:
                    continue
                nn = self._task_nodes.get(task.uid, task.node_name)
                if nn:
                    out.append((task.pod, nn))
        return out


def inter_pod_affinity_score(
    pod: core.Pod,
    nodes: List[NodeInfo],
    all_nodes: Dict[str, NodeInfo],
    assigned_pods: List[tuple],
) -> Dict[str, float]:
    """Preferred pod (anti-)affinity scoring, the used subset of the
    vendored InterPodAffinityPriority: per node, sum the weights of
    preferred terms satisfied by pods in the node's topology domain
    (affinity adds weight, anti-affinity subtracts), then normalize to
    0..10 like CalculateAntiAffinityPriority's reduce."""
    raw: Dict[str, float] = {}
    aff_terms = _preferred_terms(pod, "podAffinity")
    anti_terms = _preferred_terms(pod, "podAntiAffinity")
    if not aff_terms and not anti_terms:
        return {}

    for node in nodes:
        score = 0.0
        for p in aff_terms:
            term = p.get("podAffinityTerm") or {}
            weight = float(p.get("weight", 0))
            for ep, nn in assigned_pods:
                other = all_nodes.get(nn)
                if other is None or other.node is None:
                    continue
                if _same_topology(node.node, other.node, term.get("topologyKey", DEFAULT_TOPOLOGY_KEY)):
                    if _term_matches_pod(term, pod, ep):
                        score += weight
        for p in anti_terms:
            term = p.get("podAffinityTerm") or {}
            weight = float(p.get("weight", 0))
            for ep, nn in assigned_pods:
                other = all_nodes.get(nn)
                if other is None or other.node is None:
                    continue
                if _same_topology(node.node, other.node, term.get("topologyKey", DEFAULT_TOPOLOGY_KEY)):
                    if _term_matches_pod(term, pod, ep):
                        score -= weight
        raw[node.name] = score

    max_score = max(raw.values(), default=0.0)
    min_score = min(raw.values(), default=0.0)
    spread = max_score - min_score
    if spread == 0:
        return {n: 0.0 for n in raw}
    return {n: 10.0 * (s - min_score) / spread for n, s in raw.items()}
