"""Deploy packaging tests (the helm-chart-equivalent renderer).

Reference: installer/helm/chart/volcano/values.yaml + templates/ — the
chart parametrizes image names/tags, pull secret, and the scheduler
policy file, and stamps out one Deployment per daemon; these tests pin
the same parametrization surface on the renderer in
volcano_tpu/deploy/package.py, now rendering the multi-process bus
topology (vtpu-apiserver + scheduler + controllers + admission, all
wired with --bus).
"""

import pytest
import yaml

from volcano_tpu.deploy.package import (
    apply_set,
    DEFAULT_VALUES,
    load_values,
    merge_values,
    render,
    render_yaml,
)

BUS_URL = "tcp://volcano-tpu-apiserver.volcano-tpu-system.svc:7180"


def _by_name(manifests):
    return {m["metadata"]["name"] + "/" + m["kind"]: m for _, m in manifests}


def _deployment(manifests, daemon):
    return _by_name(manifests)[f"volcano-tpu-{daemon}/Deployment"]


def _container(manifests, daemon, name=None):
    containers = _deployment(manifests, daemon)["spec"]["template"]["spec"]["containers"]
    if name is None:
        return containers[0]
    return next(c for c in containers if c["name"] == name)


def test_default_render_manifest_set():
    manifests = render(DEFAULT_VALUES)
    names = [fname for fname, _ in manifests]
    assert names == [
        "00-namespace.yaml", "10-scheduler-configmap.yaml",
        "20-apiserver-deployment.yaml", "21-apiserver-service.yaml",
        "30-scheduler-deployment.yaml", "31-controllers-deployment.yaml",
        "32-admission-deployment.yaml",
    ]
    # kubectl apply -f DIR walks lexically; apply order must survive it:
    # namespace first, then the apiserver before the daemons dialing it
    assert names == sorted(names)
    by_name = _by_name(manifests)
    assert by_name["volcano-tpu-system/Namespace"]
    for daemon in ("apiserver", "scheduler", "controllers", "admission"):
        dep = _deployment(manifests, daemon)
        assert dep["metadata"]["namespace"] == "volcano-tpu-system"
    # every manifest round-trips through YAML
    for _, m in manifests:
        assert yaml.safe_load(yaml.safe_dump(m)) == m


def test_every_daemon_dials_the_bus():
    """The topology claim: one apiserver serving the bus; scheduler,
    controllers, and admission all wired to it with --bus."""
    manifests = render(DEFAULT_VALUES)
    api = _container(manifests, "apiserver")
    assert api["command"][0] == "vtpu-apiserver"
    assert api["command"][api["command"].index("--port") + 1] == "7180"
    svc = _by_name(manifests)["volcano-tpu-apiserver/Service"]
    assert {"name": "bus", "port": 7180} in svc["spec"]["ports"]

    for daemon, binary in (("scheduler", "vtpu-scheduler"),
                           ("controllers", "vtpu-controllers"),
                           ("admission", "vtpu-admission")):
        cmd = _container(manifests, daemon)["command"]
        assert cmd[0] == binary
        assert cmd[cmd.index("--bus") + 1] == BUS_URL


def test_ha_replicas_get_leader_election():
    # controllers default to 2 leader-elected replicas (no accelerator
    # demand, HA is free); the scheduler defaults to 1 because every
    # scheduler pod holds a full TPU slice — a default standby would sit
    # Pending on a single-slice cluster
    manifests = render(DEFAULT_VALUES)
    dep = _deployment(manifests, "controllers")
    assert dep["spec"]["replicas"] == 2
    assert "--leader-elect" in _container(manifests, "controllers")["command"]
    assert _deployment(manifests, "scheduler")["spec"]["replicas"] == 1
    assert "--leader-elect" not in _container(manifests, "scheduler")["command"]
    # opting into scheduler HA (spare slices exist) wires the lease
    values = merge_values(DEFAULT_VALUES, {"scheduler": {"replicas": 2}})
    manifests = render(values)
    assert _deployment(manifests, "scheduler")["spec"]["replicas"] == 2
    assert "--leader-elect" in _container(manifests, "scheduler")["command"]


def test_apiserver_seeds_synthetic_nodes():
    manifests = render(DEFAULT_VALUES)
    cmd = _container(manifests, "apiserver")["command"]
    assert cmd[cmd.index("--seed-nodes") + 1] == "8"


def test_configmap_inlines_default_scheduler_conf():
    manifests = render(DEFAULT_VALUES)
    cm = _by_name(manifests)["volcano-tpu-scheduler-configmap/ConfigMap"]
    conf_text = cm["data"]["volcano-scheduler.conf"]
    parsed = yaml.safe_load(conf_text)
    assert "allocate" in parsed["actions"]
    assert parsed["tiers"]


def test_configmap_inlines_custom_conf_file(tmp_path):
    conf = tmp_path / "policy.conf"
    conf.write_text("actions: \"enqueue, allocate\"\ntiers: []\n")
    values = merge_values(
        DEFAULT_VALUES, {"basic": {"scheduler_config_file": str(conf)}})
    manifests = render(values)
    cm = _by_name(manifests)["volcano-tpu-scheduler-configmap/ConfigMap"]
    assert cm["data"]["volcano-scheduler.conf"] == conf.read_text()


def _scheduler_pods(values):
    return [m["spec"]["template"]["spec"] for _, m in render(values)
            if m["kind"] == "Deployment"
            and m["metadata"]["name"].startswith("volcano-tpu-scheduler")]


@pytest.mark.parametrize("overrides", [
    [],
    ["scheduler.replicas=2"],
    ["scheduler.shards=3"],
], ids=["single", "replicas-2", "shards-3"])
def test_scheduler_pod_holds_the_chip(overrides):
    """Every scheduler pod is one container that runs the kernels in its
    own process: it carries the TPU request and --warmup, and mounts
    nothing but its policy ConfigMap."""
    values = DEFAULT_VALUES
    for assignment in overrides:
        values = apply_set(values, assignment)
    pods = _scheduler_pods(values)
    assert len(pods) == (3 if "scheduler.shards=3" in overrides else 1)
    for spec in pods:
        (sched,) = spec["containers"]
        assert sched["name"] == "scheduler"
        assert sched["resources"] == {"limits": {"google.com/tpu": "8"}}
        assert "--warmup" in sched["command"]
        assert "env" not in sched
        assert [v["name"] for v in spec["volumes"]] == ["scheduler-config"]
        assert [m["name"] for m in sched["volumeMounts"]] == [
            "scheduler-config"]


def test_static_manifest_scheduler_holds_the_tpu_limit():
    import os

    path = os.path.join(os.path.dirname(__file__), "..",
                        "deploy", "kubernetes", "volcano-tpu.yaml")
    with open(path, "r", encoding="utf-8") as fh:
        docs = [d for d in yaml.safe_load_all(fh) if d]
    dep = next(d for d in docs if d["kind"] == "Deployment"
               and d["metadata"]["name"] == "volcano-tpu-scheduler")
    (sched,) = dep["spec"]["template"]["spec"]["containers"]
    assert sched["command"][0] == "vtpu-scheduler"
    assert sched["resources"]["limits"] == {"google.com/tpu": "8"}
    assert "--warmup" in sched["command"]


def test_leftover_compute_plane_values_file_refused():
    # an operator's old values file must not silently lose its chip
    # count: the removed section is named in the error
    with pytest.raises(ValueError, match="compute_plane"):
        load_values("compute_plane:\n  tpu_chips: 4\n")


def test_leftover_compute_plane_set_refused():
    with pytest.raises(ValueError, match="compute_plane"):
        apply_set(DEFAULT_VALUES, "compute_plane.tpu_chips=4")
    # the CLI reports it as a one-line user error
    import contextlib
    import io

    from volcano_tpu.cmd.package import main

    err = io.StringIO()
    with contextlib.redirect_stderr(err):
        assert main(["template", "--set", "compute_plane.enabled=false"]) == 2
    assert "compute_plane" in err.getvalue()


def test_null_scalar_keeps_default():
    values = load_values("scheduler:\n  port:\n  nodes: 4\n")
    assert values["scheduler"]["port"] == 8080
    assert values["scheduler"]["nodes"] == 4
    render(values)


def test_values_file_merge_and_image_pull_secret():
    values = load_values(yaml.safe_dump({
        "basic": {
            "release_name": "vt-prod",
            "namespace": "prod",
            "image_tag_version": "v1.2.3",
            "image_pull_secret": "regcred",
        },
    }))
    # untouched defaults survive the merge
    assert values["scheduler"]["port"] == 8080
    manifests = render(values)
    by_name = _by_name(manifests)
    dep = by_name["vt-prod-scheduler/Deployment"]
    spec = dep["spec"]["template"]["spec"]
    assert spec["containers"][0]["image"] == "volcano-tpu:v1.2.3"
    cmd = spec["containers"][0]["command"]
    assert cmd[cmd.index("--bus") + 1] == "tcp://vt-prod-apiserver.prod.svc:7180"
    # every daemon pod can pull from the private registry
    for daemon in ("apiserver", "scheduler", "controllers", "admission"):
        d = by_name[f"vt-prod-{daemon}/Deployment"]
        assert d["spec"]["template"]["spec"]["imagePullSecrets"] == [
            {"name": "regcred"}]
    assert by_name["vt-prod-apiserver/Service"]["metadata"]["namespace"] == "prod"


def test_set_overrides_with_coercion():
    values = DEFAULT_VALUES
    for assignment in ("scheduler.port=9090",
                       "bus.port=7777",
                       "prometheus.scrape=false",
                       "scheduler.tpu_chips=4",
                       "basic.image_tag_version=nightly"):
        values = apply_set(values, assignment)
    assert values["scheduler"]["port"] == 9090
    assert values["prometheus"]["scrape"] is False
    manifests = render(values)
    sched = _container(manifests, "scheduler")
    meta = _deployment(manifests, "scheduler")["spec"]["template"]["metadata"]
    assert "annotations" not in meta
    assert sched["image"] == "volcano-tpu:nightly"
    assert sched["livenessProbe"]["httpGet"]["port"] == 9090
    cmd = sched["command"]
    assert cmd[cmd.index("--bus") + 1].endswith(":7777")
    assert sched["resources"]["limits"]["google.com/tpu"] == "4"


def test_set_rejects_malformed():
    import pytest

    with pytest.raises(ValueError):
        apply_set(DEFAULT_VALUES, "no-equals-sign")
    with pytest.raises(ValueError):
        apply_set(DEFAULT_VALUES, "=value")
    # a path traversing through an existing scalar is a typo, caught at
    # parse time rather than as a render-time TypeError
    with pytest.raises(ValueError, match="is a value, not a section"):
        apply_set(DEFAULT_VALUES, "scheduler.port.http=9090")


def test_set_string_skips_coercion():
    values = apply_set(DEFAULT_VALUES, "basic.image_tag_version=20260730",
                       coerce=False)
    assert values["basic"]["image_tag_version"] == "20260730"
    # the CLI surface: --set-string renders the tag as a string
    from volcano_tpu.cmd.package import main

    import io
    import contextlib

    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        assert main(["template", "--set-string",
                     "basic.image_tag_version=20260730"]) == 0
    docs = list(yaml.safe_load_all(buf.getvalue()))
    dep = next(d for d in docs if d["kind"] == "Deployment")
    image = dep["spec"]["template"]["spec"]["containers"][0]["image"]
    assert image == "volcano-tpu:20260730"


def test_deployment_rollout_strategies():
    # Recreate only where forced: apiserver (two concurrent stores
    # behind one Service would split clients between divergent stores)
    # and scheduler (a surge pod can't place while the old pod holds
    # the TPU chips).  Controllers/admission roll normally — Recreate
    # there would guarantee a full outage on every image upgrade.
    manifests = render(DEFAULT_VALUES)
    for daemon in ("apiserver", "scheduler"):
        dep = _deployment(manifests, daemon)
        assert dep["spec"]["strategy"] == {"type": "Recreate"}
    for daemon in ("controllers", "admission"):
        dep = _deployment(manifests, daemon)
        assert dep["spec"]["strategy"] == {"type": "RollingUpdate"}


def test_render_yaml_stream_parses():
    docs = list(yaml.safe_load_all(render_yaml(DEFAULT_VALUES)))
    assert [d["kind"] for d in docs] == [
        "Namespace", "ConfigMap", "Deployment", "Service",
        "Deployment", "Deployment", "Deployment"]


def test_empty_section_header_keeps_defaults():
    # "scheduler:" with nothing under it parses as null; the merge
    # must keep the section's defaults, not crash render()
    values = load_values("scheduler:\nbasic:\n  release_name: x\n")
    assert values["scheduler"] == DEFAULT_VALUES["scheduler"]
    assert values["basic"]["release_name"] == "x"
    render(values)


def test_static_manifest_commands_parse():
    # the hand-written deploy/kubernetes manifest must stay parseable by
    # the real daemon argument parsers (a flag rename would otherwise
    # ship CrashLooping pods while all renderer tests stay green)
    import os

    path = os.path.join(os.path.dirname(__file__), "..",
                        "deploy", "kubernetes", "volcano-tpu.yaml")
    with open(path, "r", encoding="utf-8") as fh:
        docs = [d for d in yaml.safe_load_all(fh) if d]
    deployments = [d for d in docs if d["kind"] == "Deployment"]
    assert len(deployments) == 4
    seen = set()
    for dep in deployments:
        for c in dep["spec"]["template"]["spec"]["containers"]:
            binary = c["command"][0]
            seen.add(binary)
            if binary == "vtpu-apiserver":
                known = {"--listen-host", "--port", "--listen-port",
                         "--backlog-size", "--bookmark-interval",
                         "--enable-debug-stacks", "--seed-nodes",
                         "--seed-node-cpu", "--seed-node-mem",
                         "--data-dir", "--snapshot-every", "--replicas",
                         "--replica-index", "--repl-lease-ttl"}
            elif binary == "vtpu-scheduler":
                known = {"--bus", "--listen-host", "--listen-port",
                         "--leader-elect", "--leader-elect-id",
                         "--scheduler-conf", "--schedule-period",
                         "--scheduler-name",
                         "--warmup",
                         "--micro-cycles", "--micro-debounce-ms",
                         "--percentage-nodes-to-find",
                         "--minimum-feasible-nodes",
                         "--minimum-percentage-nodes-to-find",
                         "--enable-debug-stacks"}
            elif binary == "vtpu-controllers":
                known = {"--bus", "--listen-host", "--listen-port",
                         "--leader-elect", "--leader-elect-id", "--period",
                         "--enable-debug-stacks"}
            elif binary == "vtpu-admission":
                known = {"--bus", "--listen-host", "--listen-port",
                         "--leader-elect", "--leader-elect-id",
                         "--gate-pods", "--enable-debug-stacks"}
            else:
                raise AssertionError(f"unexpected binary {binary}")
            flags = {a for a in c["command"][1:] if a.startswith("--")}
            assert flags <= known, (binary, flags - known)
    assert {"vtpu-apiserver", "vtpu-scheduler", "vtpu-controllers",
            "vtpu-admission"} <= seen


def test_static_manifest_matches_renderer():
    # deploy/kubernetes/volcano-tpu.yaml IS the rendered default output
    # (plus the header comment) — regenerate it when values change:
    #   python -m volcano_tpu.cmd.package template > ...
    import os

    path = os.path.join(os.path.dirname(__file__), "..",
                        "deploy", "kubernetes", "volcano-tpu.yaml")
    with open(path, "r", encoding="utf-8") as fh:
        static = [d for d in yaml.safe_load_all(fh) if d]
    rendered = [m for _, m in render(DEFAULT_VALUES)]
    assert static == rendered


def test_chart_values_file_matches_defaults():
    # deploy/chart/values.yaml documents the defaults; merging it over
    # DEFAULT_VALUES must be a no-op or the two sources have drifted
    import os

    path = os.path.join(os.path.dirname(__file__), "..",
                        "deploy", "chart", "values.yaml")
    with open(path, "r", encoding="utf-8") as fh:
        assert load_values(fh.read()) == DEFAULT_VALUES


def test_rendered_scheduler_command_parses():
    # the scheduler Deployment command must be accepted verbatim by the
    # real vtpu-scheduler argument parser and carry the mounted conf +
    # the same port the probe points at
    import argparse

    from volcano_tpu.cmd.scheduler import add_common_args

    manifests = render(
        merge_values(DEFAULT_VALUES, {"scheduler": {"replicas": 2}}))
    container = _container(manifests, "scheduler")
    cmd = container["command"]
    assert cmd[0] == "vtpu-scheduler"

    parser = argparse.ArgumentParser()
    parser.add_argument("--scheduler-conf", default="")
    parser.add_argument("--schedule-period", type=float, default=1.0)
    parser.add_argument("--micro-cycles", action="store_true")
    parser.add_argument("--warmup", action="store_true")
    add_common_args(parser)
    args = parser.parse_args(cmd[1:])
    assert args.micro_cycles is True  # the deployed default is event-driven
    assert args.warmup is True  # the kernels compile before cycle one
    assert args.bus == BUS_URL
    assert args.listen_host == "0.0.0.0"
    assert args.listen_port == 8080
    assert args.leader_elect is True
    assert args.scheduler_conf == "/etc/volcano-tpu/volcano-scheduler.conf"
    # the conf path the command reads is inside the ConfigMap mount
    mount = next(m for m in container["volumeMounts"]
                 if m["name"] == "scheduler-config")
    assert args.scheduler_conf.startswith(mount["mountPath"] + "/")
    # probe port agrees with the port the process actually binds
    assert container["livenessProbe"]["httpGet"]["port"] == args.listen_port


class TestShardedFederationRendering:
    def test_shards_renders_pinned_members_no_leader_election(self):
        values = apply_set(DEFAULT_VALUES, "scheduler.shards=3")
        manifests = dict(render(values))
        # the leader-elected pair is REPLACED by three pinned members
        assert "30-scheduler-deployment.yaml" not in manifests
        for i in range(3):
            dep = manifests[f"30-scheduler-{i}-deployment.yaml"]
            assert dep["spec"]["replicas"] == 1
            cmd = dep["spec"]["template"]["spec"]["containers"][0]["command"]
            assert "--leader-elect" not in cmd
            assert cmd[cmd.index("--shards") + 1] == "3"
            assert (
                cmd[cmd.index("--shard-identity") + 1]
                == f"volcano-tpu-scheduler-{i}"
            )
            assert "--shard-lease-duration" in cmd
            # every member is one container holding its own chip
            names = [c["name"] for c in
                     dep["spec"]["template"]["spec"]["containers"]]
            assert names == ["scheduler"]

    def test_shard_member_commands_parse(self):
        import argparse

        from volcano_tpu.cmd.scheduler import add_common_args

        values = apply_set(DEFAULT_VALUES, "scheduler.shards=2")
        dep = dict(render(values))["30-scheduler-1-deployment.yaml"]
        cmd = dep["spec"]["template"]["spec"]["containers"][0]["command"]
        parser = argparse.ArgumentParser()
        parser.add_argument("--scheduler-conf", default="")
        parser.add_argument("--micro-cycles", action="store_true")
        parser.add_argument("--warmup", action="store_true")
        parser.add_argument("--shards", type=int, default=0)
        parser.add_argument("--shard-identity", default="")
        parser.add_argument("--shard-lease-duration", type=float,
                            default=2.0)
        add_common_args(parser)
        args = parser.parse_args(cmd[1:])
        assert args.shards == 2
        assert args.shard_identity == "volcano-tpu-scheduler-1"
        assert args.bus == BUS_URL

    def test_shard_autoscale_flag_renders_on_every_member(self):
        values = apply_set(DEFAULT_VALUES, "scheduler.shards=2")
        values = apply_set(values, "scheduler.shard_autoscale=true")
        manifests = dict(render(values))
        for i in range(2):
            dep = manifests[f"30-scheduler-{i}-deployment.yaml"]
            cmd = dep["spec"]["template"]["spec"]["containers"][0]["command"]
            assert cmd[cmd.index("--shard-autoscale") + 1] == "on"
        # off by default: the static fleet stays static
        plain = dict(render(apply_set(DEFAULT_VALUES,
                                      "scheduler.shards=2")))
        for i in range(2):
            dep = plain[f"30-scheduler-{i}-deployment.yaml"]
            cmd = dep["spec"]["template"]["spec"]["containers"][0]["command"]
            assert "--shard-autoscale" not in cmd

    def test_shards_off_output_unchanged(self):
        # shards=0 (the default) must render exactly the classic
        # topology — the pinned static manifest stays valid
        assert dict(render(DEFAULT_VALUES)).keys() == dict(
            render(apply_set(DEFAULT_VALUES, "scheduler.shards=0"))
        ).keys()
        assert "30-scheduler-deployment.yaml" in dict(
            render(DEFAULT_VALUES))


class TestReplicatedApiserverRendering:
    def test_default_single_apiserver_is_durable(self):
        # apiserver.replicas=1 keeps the classic one-Deployment shape,
        # now with a WAL data dir (emptyDir) so container restarts
        # resume watch cursors instead of forcing a 410 relist storm
        manifests = dict(render(DEFAULT_VALUES))
        dep = manifests["20-apiserver-deployment.yaml"]
        c = dep["spec"]["template"]["spec"]["containers"][0]
        cmd = c["command"]
        assert cmd[cmd.index("--data-dir") + 1] == "/var/lib/vtpu"
        assert "--replicas" not in cmd
        mount = next(m for m in c["volumeMounts"] if m["name"] == "bus-data")
        assert mount["mountPath"] == "/var/lib/vtpu"
        assert {"name": "bus-data", "emptyDir": {}} in (
            dep["spec"]["template"]["spec"]["volumes"]
        )
        assert "21-apiserver-service.yaml" in manifests

    def test_replicas_render_per_replica_deployments_and_services(self):
        values = apply_set(DEFAULT_VALUES, "apiserver.replicas=3")
        manifests = dict(render(values))
        assert "20-apiserver-deployment.yaml" not in manifests
        expected_list = ",".join(
            f"tcp://volcano-tpu-apiserver-{i}.volcano-tpu-system.svc:7180"
            for i in range(3)
        )
        for i in range(3):
            dep = manifests[f"20-apiserver-{i}-deployment.yaml"]
            assert dep["spec"]["replicas"] == 1
            cmd = dep["spec"]["template"]["spec"]["containers"][0]["command"]
            assert cmd[cmd.index("--replicas") + 1] == expected_list
            assert cmd[cmd.index("--replica-index") + 1] == str(i)
            assert "--repl-lease-ttl" in cmd
            svc = manifests[f"21-apiserver-{i}-service.yaml"]
            assert svc["spec"]["selector"] == {
                "app": f"volcano-tpu-apiserver-{i}"
            }
        # every daemon dials the FULL endpoint list
        for fname, m in manifests.items():
            if m.get("kind") != "Deployment" or "apiserver" in fname:
                continue
            for c in m["spec"]["template"]["spec"]["containers"]:
                cmd = c["command"]
                if "--bus" in cmd:
                    assert cmd[cmd.index("--bus") + 1] == expected_list, fname

    def test_replicated_apiserver_command_parses(self):
        # the rendered replica command must be accepted verbatim by the
        # REAL vtpu-apiserver argument parser (a flag rename would
        # otherwise ship CrashLooping pods while renderer tests stay
        # green)
        values = apply_set(DEFAULT_VALUES, "apiserver.replicas=3")
        dep = dict(render(values))["20-apiserver-1-deployment.yaml"]
        cmd = dep["spec"]["template"]["spec"]["containers"][0]["command"]
        assert cmd[0] == "vtpu-apiserver"
        ns = _parse_apiserver_cmd(cmd[1:])
        assert ns.replica_index == 1
        assert ns.data_dir == "/var/lib/vtpu"
        assert len(ns.replicas.split(",")) == 3
        assert ns.repl_lease_ttl == 2.0


def _parse_apiserver_cmd(argv):
    """Parse argv with vtpu-apiserver's REAL parser: main() builds a
    plain ArgumentParser inline, so spy on parse_args and stop main()
    before it would start the daemon."""
    import argparse
    from unittest import mock

    from volcano_tpu.cmd import apiserver as apiserver_cmd

    captured = {}
    real_parse = argparse.ArgumentParser.parse_args

    def spy(self, args=None, namespace=None):
        ns = real_parse(self, args, namespace)
        captured["ns"] = ns
        raise SystemExit(0)

    with mock.patch.object(argparse.ArgumentParser, "parse_args", spy):
        try:
            apiserver_cmd.main(argv)
        except SystemExit:
            pass
    return captured["ns"]
