"""Parametrized deploy packaging — the helm-chart equivalent.

Reference: installer/helm/chart/volcano/{Chart.yaml,values.yaml,
templates/{scheduler,controllers,admission}.yaml}.  The reference ships
a Helm chart whose values.yaml parametrizes image names/tags and the
scheduler policy file, and whose templates stamp out one Deployment per
daemon.  This build renders the same topology with no Helm in the
image: a values tree (defaults below, overridable via YAML file and
``--set`` paths, same precedence helm uses) fed through ``render()``
into the full manifest set.

Topology rendered (the reference's multi-binary deployment, carried by
the out-of-process bus in volcano_tpu/bus):
  - Namespace
  - ConfigMap holding the scheduler policy (templates/scheduler.yaml's
    ``{{ .Files.Glob .Values.basic.scheduler_config_file }}`` inlining)
  - ``<name>-apiserver`` Deployment + Service: the vtpu-apiserver
    daemon serving the bus over TCP — the store every other daemon
    dials with ``--bus``.
  - ``<name>-scheduler`` Deployment: vtpu-scheduler over the bus; when
    ``replicas > 1`` the copies run ConfigMap-lease leader election
    THROUGH the bus, so a killed pod's standby takes over — real
    cross-pod HA (opt-in: every scheduler pod demands a TPU slice, so
    a standby needs spare accelerator capacity; see scheduler.replicas
    below).  The scheduler container runs the device kernels in its
    own process, so it carries the TPU request itself and compiles
    them at start-up (``--warmup``).
  - ``<name>-controllers`` Deployment: two leader-elected replicas by
    default — controllers demand no accelerator, so HA is free.
  - ``<name>-admission`` Deployment: registers its webhooks over the
    bus; the apiserver forwards admission reviews to it.
"""

from __future__ import annotations

import copy
from typing import Any, Dict, List, Tuple

# Mirrors the reference's values.yaml key shape (basic.image_tag_version
# etc., installer/helm/chart/volcano/values.yaml:1-9) with TPU-build
# additions grouped per daemon.
DEFAULT_VALUES: Dict[str, Any] = {
    "basic": {
        "release_name": "volcano-tpu",
        "namespace": "volcano-tpu-system",
        "image_name": "volcano-tpu",
        "image_tag_version": "latest",
        "image_pull_secret": "",
        # empty -> the built-in DEFAULT_SCHEDULER_CONF is inlined
        "scheduler_config_file": "",
    },
    "bus": {
        "port": 7180,
    },
    "apiserver": {
        "port": 8083,
        "backlog_size": 4096,
        # WAL + snapshot directory (bus/wal.py): every store
        # transaction is fsynced before acking and a restarted pod
        # resumes watch cursors instead of forcing a cluster-wide 410
        # relist.  Backed by an emptyDir by default — durable across
        # container restarts on the same node; replication (below) is
        # what covers node loss.  Point it at a PVC mount for
        # single-replica node-loss durability.
        "data_dir": "/var/lib/vtpu",
        # replicated persistent bus: N > 1 renders one apiserver
        # Deployment + Service PER REPLICA (stable per-replica DNS is
        # the static membership list), wires every daemon's --bus to
        # the full endpoint list, and the replicas elect a leader —
        # writes quorum-commit, a SIGKILLed leader is replaced by the
        # most-advanced survivor within one lease TTL.
        "replicas": 1,
        "repl_lease_ttl": 2.0,
    },
    "scheduler": {
        # synthetic node pool the apiserver seeds (kubelet substitute)
        "nodes": 8,
        "port": 8080,
        # the TPU request of the scheduler container, which runs the
        # device kernels in its own process
        "tpu_resource": "google.com/tpu",
        "tpu_chips": 8,
        # every scheduler pod demands a full TPU slice, so a standby
        # replica needs SPARE accelerator capacity — on a single-slice
        # cluster it would sit Pending and the kubelet's restart of a
        # dead leader beats any takeover.
        # Default to 1; set 2 (adds --leader-elect) where slices exist.
        "replicas": 1,
        # event-driven scheduling (adds --micro-cycles): wake on watch
        # events and run debounced micro-cycles between the periodic
        # full cycles.  Bindings stay bit-identical to the fixed-period
        # loop; submit→bind latency under churn drops from ~a period to
        # ~a cycle.  Off only for debugging cadence-sensitive policies.
        "micro_cycles": True,
        # sharded scheduler federation: N > 1 renders N shard-pinned
        # scheduler Deployments (each --shards N with a stable
        # identity), REPLACING the leader-elected standby pair — every
        # member is active over its own node slice, ownership moves via
        # bus-backed shard leases, and a dead member's slices are
        # absorbed by survivors within one lease TTL.  Each member pod
        # still demands a full TPU slice.  0/1 keeps the single
        # scheduler (with `replicas: 2` leader-elected standby HA).
        "shards": 0,
        "shard_lease_duration": 2.0,
        # SLO-driven shard autoscaling (adds --shard-autoscale on to
        # every member; needs shards > 1 so a standby member exists to
        # absorb grown slices): the member holding shard 0's lease
        # moves the map's shard count one step at a time from
        # sustained fleet p99 / queue-depth signals with hysteresis +
        # cooldown, and every member ADOPTS the map's count instead of
        # refusing a mismatch.  The controller moves the TARGET only —
        # size the member pool (scheduler.shards here, or a cluster
        # autoscaler on the Deployment set) to the ceiling you want
        # reachable.  Off by default: the rendered fleet is static
        # unless an operator opts in.
        "shard_autoscale": False,
    },
    "controllers": {
        "port": 8081,
        # no accelerator demand — cross-pod HA is free here
        "replicas": 2,
    },
    "admission": {
        "port": 8082,
    },
    "prometheus": {
        "scrape": True,
    },
}


def merge_values(base: Dict[str, Any], override: Dict[str, Any]) -> Dict[str, Any]:
    """Deep-merge ``override`` onto ``base`` (helm's values precedence:
    later sources win per-key, dicts merge recursively)."""
    out = copy.deepcopy(base)
    for key, val in (override or {}).items():
        if isinstance(val, dict) and isinstance(out.get(key), dict):
            out[key] = merge_values(out[key], val)
        elif val is None and out.get(key) is not None:
            # a bare section header ("scheduler:") or blank scalar
            # ("port:") parses as null — keep the default rather than
            # clobbering the value and crashing render() later
            continue
        else:
            out[key] = copy.deepcopy(val)
    return out


#: values sections that no longer exist, and where their settings went.
#: Refused rather than ignored: a dropped override (say a chip count)
#: would otherwise render the default without a word.
_REMOVED_SECTIONS = {
    "compute_plane": "the kernels run in the scheduler container; set "
    "scheduler.tpu_resource and scheduler.tpu_chips instead",
}


def _refuse_removed(section: str) -> None:
    if section in _REMOVED_SECTIONS:
        raise ValueError(
            f"values section {section!r} was removed: "
            f"{_REMOVED_SECTIONS[section]}")


def _coerce(text: str) -> Any:
    """--set value coercion: helm's scalar parsing (int, bool, string)."""
    low = text.lower()
    if low in ("true", "false"):
        return low == "true"
    try:
        return int(text)
    except ValueError:
        return text


def apply_set(values: Dict[str, Any], assignment: str,
              coerce: bool = True) -> Dict[str, Any]:
    """Apply one ``--set a.b.c=v`` override (helm --set path syntax).

    ``coerce=False`` is the ``--set-string`` escape hatch: the value
    stays a string even when it looks numeric or boolean."""
    if "=" not in assignment:
        raise ValueError(f"--set needs key=value, got {assignment!r}")
    path, _, raw = assignment.partition("=")
    keys = [k for k in path.split(".") if k]
    if not keys:
        raise ValueError(f"--set has empty key path: {assignment!r}")
    _refuse_removed(keys[0])
    out = copy.deepcopy(values)
    node = out
    for k in keys[:-1]:
        nxt = node.get(k)
        if nxt is None:
            nxt = {}
            node[k] = nxt
        elif not isinstance(nxt, dict):
            # traversing through an existing scalar is a path typo —
            # surface it here, not as a render-time TypeError
            raise ValueError(
                f"--set path {path!r}: {k!r} is a value, not a section")
        node = nxt
    node[keys[-1]] = _coerce(raw) if coerce else raw
    return out


def load_values(text: str) -> Dict[str, Any]:
    """Parse a values YAML document and merge it over the defaults."""
    import yaml

    raw = yaml.safe_load(text) or {}
    if not isinstance(raw, dict):
        raise ValueError("values file must be a YAML mapping")
    for section in raw:
        _refuse_removed(section)
    return merge_values(DEFAULT_VALUES, raw)


def _scheduler_conf_text(values: Dict[str, Any]) -> str:
    path = values["basic"].get("scheduler_config_file") or ""
    if path:
        with open(path, "r", encoding="utf-8") as fh:
            return fh.read()
    # conf's own import of framework.arguments re-enters conf when conf
    # is imported first; initializing the framework package up front
    # keeps this module importable standalone
    import volcano_tpu.framework  # noqa: F401
    from volcano_tpu.conf import DEFAULT_SCHEDULER_CONF

    return DEFAULT_SCHEDULER_CONF.strip() + "\n"


def _deployment(name: str, ns: str, labels: Dict[str, str],
                containers: List[Dict[str, Any]],
                volumes: List[Dict[str, Any]],
                replicas: int,
                annotations: Dict[str, str],
                image_pull_secret: str,
                strategy: str = "RollingUpdate") -> Dict[str, Any]:
    pod_spec: Dict[str, Any] = {"containers": containers}
    if volumes:
        pod_spec["volumes"] = volumes
    if image_pull_secret:
        pod_spec["imagePullSecrets"] = [{"name": image_pull_secret}]
    template_meta: Dict[str, Any] = {"labels": labels}
    if annotations:
        template_meta["annotations"] = annotations
    return {
        "apiVersion": "apps/v1",
        "kind": "Deployment",
        "metadata": {"name": name, "namespace": ns, "labels": labels},
        "spec": {
            "replicas": replicas,
            # Recreate only where it is forced: the apiserver (two
            # concurrent store instances behind one Service would split
            # clients between divergent stores) and the scheduler (a
            # surge pod could never place — the old pod holds the node's
            # TPU chips until it dies).  Controllers/admission roll
            # normally; leader election covers the overlap.
            "strategy": {"type": strategy},
            "selector": {"matchLabels": labels},
            "template": {"metadata": template_meta, "spec": pod_spec},
        },
    }


def _probe(port: int) -> Dict[str, Any]:
    return {"httpGet": {"path": "/healthz", "port": port},
            "periodSeconds": 10}


def render(values: Dict[str, Any]) -> List[Tuple[str, Dict[str, Any]]]:
    """Render the manifest set from a values tree.

    Returns ``[(filename, manifest_dict), ...]`` in apply order, the
    template expansion the reference delegates to Helm."""
    basic = values["basic"]
    name = basic["release_name"]
    ns = basic["namespace"]
    image = f"{basic['image_name']}:{basic['image_tag_version']}"
    pull_secret = basic.get("image_pull_secret", "")
    bus_port = int(values["bus"]["port"])
    api_port = int(values["apiserver"]["port"])
    sched_port = int(values["scheduler"]["port"])
    ctrl_port = int(values["controllers"]["port"])
    adm_port = int(values["admission"]["port"])
    api_replicas = int(values["apiserver"].get("replicas", 1) or 1)
    data_dir = values["apiserver"].get("data_dir", "") or ""
    if api_replicas > 1:
        # per-replica Services are the static membership list: every
        # daemon (and every replica) dials the same ordered endpoints
        bus_urls = [
            f"tcp://{name}-apiserver-{i}.{ns}.svc:{bus_port}"
            for i in range(api_replicas)
        ]
        bus_url = ",".join(bus_urls)
    else:
        bus_urls = [f"tcp://{name}-apiserver.{ns}.svc:{bus_port}"]
        bus_url = bus_urls[0]

    def scrape(port: int) -> Dict[str, str]:
        if not values["prometheus"]["scrape"]:
            return {}
        return {"prometheus.io/scrape": "true",
                "prometheus.io/port": str(port)}

    manifests: List[Tuple[str, Dict[str, Any]]] = []

    # filenames carry the apply order — kubectl apply -f DIR walks the
    # directory lexically, and the Namespace must exist before anything
    # placed inside it, the apiserver before the daemons that dial it
    manifests.append(("00-namespace.yaml", {
        "apiVersion": "v1",
        "kind": "Namespace",
        "metadata": {"name": ns},
    }))

    manifests.append(("10-scheduler-configmap.yaml", {
        "apiVersion": "v1",
        "kind": "ConfigMap",
        "metadata": {"name": f"{name}-scheduler-configmap", "namespace": ns},
        "data": {"volcano-scheduler.conf": _scheduler_conf_text(values)},
    }))

    # ---- apiserver: the bus every other daemon dials ----
    # One Deployment (+ Service) per replica: replicas need stable,
    # individually-addressable endpoints (the static membership list),
    # and each owns its own WAL volume.  A single replica keeps the
    # original one-Deployment shape.
    def apiserver_manifests(suffix: str, index: int):
        deploy_name = f"{name}-apiserver{suffix}"
        api_labels = {"app": deploy_name}
        command = [
            "vtpu-apiserver",
            "--listen-host", "0.0.0.0",
            "--port", str(bus_port),
            "--listen-port", str(api_port),
            "--backlog-size", str(int(values["apiserver"]["backlog_size"])),
            "--seed-nodes", str(int(values["scheduler"]["nodes"])),
        ]
        volumes: List[Dict[str, Any]] = []
        mounts: List[Dict[str, Any]] = []
        if data_dir:
            command += ["--data-dir", data_dir]
            volumes.append({"name": "bus-data", "emptyDir": {}})
            mounts.append({"name": "bus-data", "mountPath": data_dir})
        if api_replicas > 1:
            command += [
                "--replicas", bus_url,
                "--replica-index", str(index),
                "--repl-lease-ttl",
                str(values["apiserver"].get("repl_lease_ttl", 2.0)),
            ]
        container: Dict[str, Any] = {
            "name": "apiserver",
            "image": image,
            "command": command,
            "livenessProbe": _probe(api_port),
            "ports": [
                {"containerPort": bus_port, "name": "bus"},
                {"containerPort": api_port, "name": "metrics"},
            ],
        }
        if mounts:
            container["volumeMounts"] = mounts
        deployment = _deployment(
            deploy_name, ns, api_labels,
            containers=[container],
            volumes=volumes,
            # one pod per Deployment either way: a replica IS the unit
            # of replication (k8s surge copies would split the WAL),
            # and the single-apiserver store is the consistency point
            replicas=1,
            annotations=scrape(api_port),
            image_pull_secret=pull_secret,
            strategy="Recreate",
        )
        service = {
            "apiVersion": "v1",
            "kind": "Service",
            "metadata": {"name": deploy_name, "namespace": ns,
                         "labels": api_labels},
            "spec": {
                "selector": api_labels,
                "ports": [
                    {"name": "bus", "port": bus_port},
                    {"name": "metrics", "port": api_port},
                ],
            },
        }
        return deployment, service

    if api_replicas > 1:
        for i in range(api_replicas):
            dep, svc = apiserver_manifests(f"-{i}", i)
            manifests.append((f"20-apiserver-{i}-deployment.yaml", dep))
            manifests.append((f"21-apiserver-{i}-service.yaml", svc))
    else:
        dep, svc = apiserver_manifests("", 0)
        manifests.append(("20-apiserver-deployment.yaml", dep))
        manifests.append(("21-apiserver-service.yaml", svc))

    # ---- scheduler: leader-elected replicas, or N shard-pinned
    # federation members when scheduler.shards > 1; each pod one
    # container holding the chip ----
    sched_replicas = int(values["scheduler"].get("replicas", 1))
    tpu_resource = values["scheduler"]["tpu_resource"]
    tpu_chips = values["scheduler"]["tpu_chips"]
    shards = int(values["scheduler"].get("shards", 0) or 0)

    def scheduler_manifest(fname: str, deploy_name: str,
                           extra_args: List[str],
                           leader_elect: bool) -> Tuple[str, Dict[str, Any]]:
        sched_cmd = [
            "vtpu-scheduler",
            "--bus", bus_url,
            "--listen-host", "0.0.0.0",
            "--listen-port", str(sched_port),
            "--scheduler-conf", "/etc/volcano-tpu/volcano-scheduler.conf",
            "--warmup",
        ]
        if values["scheduler"].get("micro_cycles"):
            sched_cmd.append("--micro-cycles")
        if leader_elect:
            sched_cmd.append("--leader-elect")
        sched_cmd.extend(extra_args)
        scheduler: Dict[str, Any] = {
            "name": "scheduler",
            "image": image,
            "command": sched_cmd,
            "volumeMounts": [
                {"name": "scheduler-config",
                 "mountPath": "/etc/volcano-tpu"},
            ],
            "livenessProbe": _probe(sched_port),
            "ports": [{"containerPort": sched_port, "name": "metrics"}],
            "resources": {
                "limits": {tpu_resource: str(tpu_chips)},
            },
        }
        return (fname, _deployment(
            deploy_name, ns, {"app": deploy_name},
            containers=[scheduler],
            volumes=[{"name": "scheduler-config",
                      "configMap": {"name": f"{name}-scheduler-configmap"}}],
            # federation members are shard-pinned singletons: their HA
            # is the lease plane itself (survivors absorb an expired
            # member's slices), not a standby replica
            replicas=1 if shards > 1 else sched_replicas,
            annotations=scrape(sched_port),
            image_pull_secret=pull_secret,
            strategy="Recreate",
        ))

    if shards > 1:
        lease = values["scheduler"].get("shard_lease_duration", 2.0)
        autoscale_args = (
            ["--shard-autoscale", "on"]
            if values["scheduler"].get("shard_autoscale") else []
        )
        for i in range(shards):
            manifests.append(scheduler_manifest(
                f"30-scheduler-{i}-deployment.yaml",
                f"{name}-scheduler-{i}",
                extra_args=[
                    "--shards", str(shards),
                    "--shard-identity", f"{name}-scheduler-{i}",
                    "--shard-lease-duration", str(lease),
                    *autoscale_args,
                ],
                leader_elect=False,
            ))
    else:
        manifests.append(scheduler_manifest(
            "30-scheduler-deployment.yaml", f"{name}-scheduler",
            extra_args=[], leader_elect=sched_replicas > 1,
        ))

    # ---- controllers ----
    ctrl_replicas = int(values["controllers"].get("replicas", 1))
    ctrl_cmd = [
        "vtpu-controllers",
        "--bus", bus_url,
        "--listen-host", "0.0.0.0",
        "--listen-port", str(ctrl_port),
    ]
    if ctrl_replicas > 1:
        ctrl_cmd.append("--leader-elect")
    manifests.append(("31-controllers-deployment.yaml", _deployment(
        f"{name}-controllers", ns, {"app": f"{name}-controllers"},
        containers=[{
            "name": "controllers",
            "image": image,
            "command": ctrl_cmd,
            "livenessProbe": _probe(ctrl_port),
            "ports": [{"containerPort": ctrl_port, "name": "metrics"}],
        }],
        volumes=[], replicas=ctrl_replicas,
        annotations=scrape(ctrl_port),
        image_pull_secret=pull_secret,
    )))

    # ---- admission ----
    manifests.append(("32-admission-deployment.yaml", _deployment(
        f"{name}-admission", ns, {"app": f"{name}-admission"},
        containers=[{
            "name": "admission",
            "image": image,
            "command": [
                "vtpu-admission",
                "--bus", bus_url,
                "--listen-host", "0.0.0.0",
                "--listen-port", str(adm_port),
            ],
            "livenessProbe": _probe(adm_port),
            "ports": [{"containerPort": adm_port, "name": "metrics"}],
        }],
        volumes=[], replicas=1,
        annotations=scrape(adm_port),
        image_pull_secret=pull_secret,
    )))

    return manifests


def render_yaml(values: Dict[str, Any]) -> str:
    """The ``helm template`` equivalent: one multi-document YAML stream."""
    import yaml

    docs = [yaml.safe_dump(m, sort_keys=False, default_flow_style=False)
            for _, m in render(values)]
    return "---\n".join(docs)
