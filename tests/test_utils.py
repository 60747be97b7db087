"""Priority queue, serde, and quantity tests."""

from volcano_tpu.apis import core, serde
from volcano_tpu.apis.batch import Job, JobSpec, TaskSpec
from volcano_tpu.apis.quantity import parse_quantity
from volcano_tpu.utils import PriorityQueue


class TestPriorityQueue:
    def test_orders_by_less_fn(self):
        pq = PriorityQueue(lambda a, b: a < b)
        for x in [5, 1, 3]:
            pq.push(x)
        assert [pq.pop(), pq.pop(), pq.pop()] == [1, 3, 5]

    def test_stable_for_equal_items(self):
        pq = PriorityQueue(lambda a, b: False)  # everything equal
        for x in ["a", "b", "c"]:
            pq.push(x)
        assert [pq.pop(), pq.pop(), pq.pop()] == ["a", "b", "c"]

    def test_empty_pop_returns_none(self):
        pq = PriorityQueue(lambda a, b: a < b)
        assert pq.empty()
        assert pq.pop() is None


class TestQuantity:
    def test_suffixes(self):
        assert parse_quantity("100m") == 0.1
        assert parse_quantity("1Gi") == 1024**3
        assert parse_quantity("2k") == 2000
        assert parse_quantity(3) == 3.0
        assert parse_quantity("1.5") == 1.5


class TestSerde:
    def test_pod_round_trip(self):
        pod = core.Pod(
            metadata=core.ObjectMeta(name="p1", namespace="ns", labels={"a": "b"}),
            spec=core.PodSpec(
                containers=[core.Container(resources={"requests": {"cpu": "1"}})],
                node_selector={"disk": "ssd"},
                tolerations=[core.Toleration(key="k", effect="NoSchedule")],
            ),
        )
        data = pod.to_dict()
        assert data["kind"] == "Pod"
        assert data["spec"]["nodeSelector"] == {"disk": "ssd"}
        back = core.Pod.from_dict(data)
        assert back.metadata.name == "p1"
        assert back.spec.tolerations[0].key == "k"
        assert back.spec.containers[0].resources["requests"]["cpu"] == "1"

    def test_camel_case_input(self):
        job = Job.from_dict(
            {
                "metadata": {"name": "j", "namespace": "ns"},
                "spec": {
                    "minAvailable": 3,
                    "tasks": [{"name": "worker", "replicas": 3}],
                    "maxRetry": 5,
                },
            }
        )
        assert job.spec.min_available == 3
        assert job.spec.tasks[0].replicas == 3
        assert job.spec.max_retry == 5

    def test_clone_is_deep(self):
        job = Job(spec=JobSpec(tasks=[TaskSpec(name="t", replicas=1)]))
        c = job.clone()
        c.spec.tasks[0].replicas = 9
        assert job.spec.tasks[0].replicas == 1


class TestNativeBuild:
    def test_built_from_source_keyed_by_hash(self, tmp_path, monkeypatch):
        import hashlib
        import os

        from volcano_tpu import native

        monkeypatch.setattr(native, "_BUILD_DIR", str(tmp_path))
        with open(native._SRC, "rb") as f:
            digest = hashlib.sha256(f.read()).hexdigest()[:16]
        so = native._build()
        assert so == os.path.join(str(tmp_path), f"baseline-{digest}.so")
        mtime = os.path.getmtime(so)
        assert native._build() == so and os.path.getmtime(so) == mtime
        assert os.listdir(tmp_path) == [os.path.basename(so)]  # no temp left
