"""Accelerator detection + provisioning (reference:
python/ray/tests/accelerators/test_tpu.py for the detection half;
autoscaler/v2 provider tests for the provisioning half). Everything runs
against injected fakes: a tmp device dir, an env mapping, and a scripted
HTTP transport — zero hardware, zero network."""

import json

import pytest

from ray_tpu.accelerators import (
    CpuAcceleratorManager,
    GceTpuNodeProvider,
    TpuAcceleratorManager,
    parse_pod_type,
)
from ray_tpu.accelerators.gce import (
    ACCEL_TYPE_ATTR,
    GCE_METADATA_URL,
    WORKER_NUMBER_ATTR,
)
from ray_tpu.autoscaler_v2 import (
    ALLOCATED,
    RAY_RUNNING,
    Instance,
    InstanceManager,
)
from ray_tpu.core.resources import detect_node_resources


class FakeTransport:
    """Scripted wire: metadata attributes + TPU REST node table. Records
    every request so tests assert the exact calls made."""

    def __init__(self, metadata=None):
        self.metadata = dict(metadata or {})
        self.nodes = {}  # name -> node dict (the cloud's view)
        self.requests = []
        self.fail_creates = 0
        self.page_size = 0  # >0: paginate GET /nodes with nextPageToken

    def request(self, method, url, body=None, headers=None, timeout=10.0):
        self.requests.append((method, url, body))
        if url.startswith(GCE_METADATA_URL):
            path = url[len(GCE_METADATA_URL) + 1 :]
            val = self.metadata.get(path)
            return (200, val) if val is not None else (404, "")
        if "/nodes" in url:
            return self._rest(method, url, body)
        return 404, ""

    def _rest(self, method, url, body):
        name = url.rsplit("/nodes", 1)[1].lstrip("/?")
        if method == "POST":
            name = url.split("nodeId=")[1]
            if self.fail_creates > 0:
                self.fail_creates -= 1
                return 429, json.dumps({"error": "quota"})
            self.nodes[name] = {
                "name": f"projects/p/locations/z/nodes/{name}",
                "state": "CREATING",
                "acceleratorType": body["acceleratorType"],
                "labels": dict(body.get("labels") or {}),
                "metadata": dict(body.get("metadata") or {}),
                "networkEndpoints": [],
            }
            return 200, json.dumps({"name": f"operations/{name}"})
        if method == "GET":
            nodes = list(self.nodes.values())
            if self.page_size and "pageToken=" not in url:
                return 200, json.dumps(
                    {"nodes": nodes[: self.page_size], "nextPageToken": "p2"}
                )
            if self.page_size:
                return 200, json.dumps({"nodes": nodes[self.page_size :]})
            return 200, json.dumps({"nodes": nodes})
        if method == "DELETE":
            if name not in self.nodes:
                return 404, json.dumps({"error": {"code": 404}})
            self.nodes.pop(name)
            return 200, "{}"
        return 405, ""

    def make_ready(self, name, hosts):
        node = self.nodes[name]
        node["state"] = "READY"
        node["networkEndpoints"] = [
            {"ipAddress": f"10.0.0.{i}"} for i in range(hosts)
        ]


# --------------------------------------------------------------- detection
def test_pod_type_parsing():
    # (version, total chips, chips/host, hosts)
    assert parse_pod_type("v5litepod-16") == ("v5e", 16, 4, 4)
    assert parse_pod_type("v5e-64") == ("v5e", 64, 4, 16)
    assert parse_pod_type("v5litepod-8") == ("v5e", 8, 8, 1)
    # v2/v3/v4/v5p suffixes count TensorCores (2 per chip, 8 per host):
    assert parse_pod_type("v4-16") == ("v4", 8, 4, 2)
    assert parse_pod_type("v4-8") == ("v4", 4, 4, 1)
    assert parse_pod_type("v5p-32") == ("v5p", 16, 4, 4)
    assert parse_pod_type("v3-32") == ("v3", 16, 4, 4)
    assert parse_pod_type("nonsense") is None


def test_chip_count_from_fake_dev_dir(tmp_path):
    for i in range(4):
        (tmp_path / f"accel{i}").touch()
    (tmp_path / "null").touch()
    mgr = TpuAcceleratorManager(dev_dir=str(tmp_path), env={}, transport=FakeTransport())
    assert mgr.get_current_node_num_accelerators() == 4


def test_chip_count_from_vfio_groups(tmp_path):
    """v5e VMs pass chips through VFIO: no /dev/accel*, one numbered IOMMU
    group per chip next to the /dev/vfio/vfio control node."""
    (tmp_path / "vfio").mkdir()
    for name in ("0", "1", "2", "3", "vfio"):
        (tmp_path / "vfio" / name).touch()
    mgr = TpuAcceleratorManager(dev_dir=str(tmp_path), env={}, transport=FakeTransport())
    assert mgr.get_current_node_num_accelerators() == 4


def test_chip_count_device_nodes_beat_env_bounds(tmp_path):
    """The one-chip v5e machine of PR 21: the image exports the 2x2 slice
    bounds but the host holds a single /dev/vfio/0, and jax finds one
    device. Registering TPU: 4 there would schedule actors onto chips that
    do not exist."""
    (tmp_path / "vfio").mkdir()
    (tmp_path / "vfio" / "0").touch()
    (tmp_path / "vfio" / "vfio").touch()
    mgr = TpuAcceleratorManager(
        dev_dir=str(tmp_path),
        env={"TPU_CHIPS_PER_HOST_BOUNDS": "2,2,1", "TPU_ACCELERATOR_TYPE": "v5litepod-4"},
        transport=FakeTransport(),
    )
    assert mgr.get_current_node_num_accelerators() == 1


def test_chip_count_env_bounds_without_device_nodes(tmp_path):
    mgr = TpuAcceleratorManager(
        dev_dir=str(tmp_path),
        env={"TPU_CHIPS_PER_HOST_BOUNDS": "2,2,1"},
        transport=FakeTransport(),
    )
    assert mgr.get_current_node_num_accelerators() == 4


def test_slice_spec_from_stubbed_metadata(tmp_path):
    """The acceptance-criteria path: pod type + topology + worker index all
    resolve from GCE metadata through the injected transport."""
    for i in range(4):
        (tmp_path / f"accel{i}").touch()
    transport = FakeTransport(
        metadata={
            ACCEL_TYPE_ATTR: "v5litepod-16",
            WORKER_NUMBER_ATTR: "2",
            "instance/attributes/instance-id": "my-slice-7",
        }
    )
    mgr = TpuAcceleratorManager(dev_dir=str(tmp_path), env={}, transport=transport)
    assert mgr.get_current_node_accelerator_type() == "v5litepod-16"
    spec = mgr.detect_slice_spec()
    assert spec is not None
    assert spec.version == "v5e"
    assert spec.slice_name == "my-slice-7"
    assert spec.hosts_per_slice == 4 and spec.chips_per_host == 4
    assert spec.total_chips == 16
    assert spec.worker_index == 2
    assert spec.topology == "4x4"  # derived: no explicit topology attribute


def test_slice_spec_gke_env_beats_metadata(tmp_path):
    transport = FakeTransport(metadata={ACCEL_TYPE_ATTR: "v5litepod-16"})
    mgr = TpuAcceleratorManager(
        dev_dir=str(tmp_path),
        env={
            "TPU_ACCELERATOR_TYPE": "v5e-64",
            "TPU_WORKER_ID": "5",
            "TPU_NAME": "gke-slice",
            "TPU_TOPOLOGY": "8x8",
        },
        transport=transport,
    )
    spec = mgr.detect_slice_spec()
    assert (spec.slice_name, spec.worker_index, spec.topology) == ("gke-slice", 5, "8x8")
    assert spec.hosts_per_slice == 16
    # Env satisfied everything: detection made no metadata requests.
    assert transport.requests == []


def test_off_tpu_host_detects_nothing(tmp_path):
    mgr = TpuAcceleratorManager(dev_dir=str(tmp_path), env={}, transport=FakeTransport())
    assert mgr.get_current_node_num_accelerators() == 0
    assert mgr.detect_slice_spec() is None


# -------------------------------------------------------------- visibility
def test_worker_visibility_env():
    mgr = TpuAcceleratorManager(env={}, transport=FakeTransport())
    env = mgr.worker_visibility_env([0, 1, 2, 3], slice_name="s", worker_index=1)
    assert env["TPU_VISIBLE_CHIPS"] == "0,1,2,3"
    assert env["TPU_CHIPS_PER_HOST_BOUNDS"] == "1,1,4"
    assert env["TPU_SLICE_NAME"] == "s"
    assert env["TPU_WORKER_ID"] == "1"


def test_visible_chip_ids_respects_inherited_restriction():
    mgr = TpuAcceleratorManager(
        env={"TPU_VISIBLE_CHIPS": "2,3"}, transport=FakeTransport()
    )
    assert mgr.visible_chip_ids(2) == [2, 3]
    unrestricted = TpuAcceleratorManager(env={}, transport=FakeTransport())
    assert unrestricted.visible_chip_ids(4) == [0, 1, 2, 3]


def test_set_current_process_visible_accelerators():
    import os

    touched = ("TPU_VISIBLE_CHIPS", "TPU_CHIPS_PER_HOST_BOUNDS", "TPU_WORKER_ID")
    saved = {k: os.environ.get(k) for k in touched}
    mgr = TpuAcceleratorManager(env={}, transport=FakeTransport())
    try:
        mgr.set_current_process_visible_accelerators([1, 3])
        assert os.environ["TPU_VISIBLE_CHIPS"] == "1,3"
        assert os.environ["TPU_CHIPS_PER_HOST_BOUNDS"] == "1,1,2"
    finally:
        # Scrub, don't monkeypatch: a leaked TPU_VISIBLE_CHIPS makes every
        # raylet subprocess later tests spawn sublease only chips {1,3}.
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


# ---------------------------------------------------------------- registry
def test_registry_and_detect_node_resources(tmp_path, monkeypatch):
    import ray_tpu.accelerators as acc

    for i in range(8):
        (tmp_path / f"accel{i}").touch()
    stub = TpuAcceleratorManager(dev_dir=str(tmp_path), env={}, transport=FakeTransport())
    acc.register_accelerator_manager(stub, override=True)
    try:
        assert acc.get_accelerator_manager("TPU") is stub
        assert acc.detect_accelerators() == {"TPU": 8.0}
        res = detect_node_resources(num_cpus=2)
        assert res["CPU"] == 2.0 and res["TPU"] == 8.0
        # Explicit num_tpus overrides the detected count entirely.
        assert detect_node_resources(num_cpus=1, num_tpus=4)["TPU"] == 4.0
        assert "TPU" not in detect_node_resources(num_cpus=1, num_tpus=0)
    finally:
        acc.register_accelerator_manager(
            TpuAcceleratorManager(), override=True
        )
    assert isinstance(acc.get_accelerator_manager("CPU"), CpuAcceleratorManager)


def test_plugin_registration():
    import ray_tpu.accelerators as acc
    from ray_tpu.accelerators import AcceleratorManager

    class NpuManager(AcceleratorManager):
        def get_resource_name(self):
            return "NPU"

        def get_current_node_num_accelerators(self):
            return 2

    acc.register_accelerator_manager(NpuManager())
    try:
        assert acc.detect_accelerators()["NPU"] == 2.0
        with pytest.raises(ValueError):
            acc.register_accelerator_manager(NpuManager())
    finally:
        acc._registry.pop("NPU", None)


# ------------------------------------------------------------ provisioning
class FakeGcs:
    """list_nodes-only GCS double: nodes appear with labels as the fake
    cloud's startup scripts would register them."""

    def __init__(self):
        self.nodes = []

    def call(self, method, *a):
        assert method == "list_nodes"
        return list(self.nodes)

    def join(self, node_id, cloud_id, worker_index=0):
        self.nodes.append(
            {
                "NodeID": node_id,
                "Alive": True,
                "Labels": {"ray_tpu_cloud_id": cloud_id, "worker_index": worker_index},
            }
        )


def _gce_provider(transport, gcs=None, **kw):
    kw.setdefault("accelerator_type", "v5litepod-16")
    return GceTpuNodeProvider(
        "proj", "us-central1-a", transport=transport, gcs=gcs,
        head_address="tcp://10.0.0.1:6380", **kw,
    )


def test_gce_create_labels_and_startup_script():
    transport = FakeTransport()
    provider = _gce_provider(transport, cluster_name="demo")
    cloud_id = provider.request(Instance("abcdef0123456789", {}))
    assert cloud_id == "raytpu-abcdef012345"
    node = transport.nodes[cloud_id]
    assert node["acceleratorType"] == "v5litepod-16"
    assert node["labels"]["ray-tpu-cluster"] == "demo"
    script = node["metadata"]["startup-script"]
    # The join command propagates the cloud-id label into the raylet so
    # ray_node_for can match machine -> ray node through the GCS.
    assert "--address tcp://10.0.0.1:6380" in script
    assert "ray_tpu_cloud_id" in script and cloud_id in script
    assert provider.poll() == {cloud_id: "pending"}


def test_gce_ready_with_all_hosts_then_ray_join():
    transport = FakeTransport()
    gcs = FakeGcs()
    provider = _gce_provider(transport, gcs=gcs)
    cloud_id = provider.request(Instance("i1", {}))
    transport.make_ready(cloud_id, hosts=4)  # v5litepod-16 = 4 hosts
    assert provider.poll() == {cloud_id: "running"}
    # Only 3 of 4 hosts joined ray: the slice is not reported up yet.
    for i in range(3):
        gcs.join(f"n{i}", cloud_id, worker_index=i)
    assert provider.ray_node_for(cloud_id) is None
    gcs.join("n3", cloud_id, worker_index=3)
    assert provider.ray_node_for(cloud_id) == "n0"  # worker 0 of the slice


def test_gce_partial_slice_is_torn_down():
    """READY but with missing worker endpoints: terminate-on-partial-
    failure — the node is deleted and reported failed."""
    transport = FakeTransport()
    provider = _gce_provider(transport)
    cloud_id = provider.request(Instance("i2", {}))
    transport.make_ready(cloud_id, hosts=2)  # 2 of 4 hosts materialized
    assert provider.poll() == {cloud_id: "failed"}
    assert cloud_id not in transport.nodes  # DELETE was issued
    deletes = [r for r in transport.requests if r[0] == "DELETE"]
    assert len(deletes) == 1


def test_gce_error_state_is_torn_down():
    transport = FakeTransport()
    provider = _gce_provider(transport)
    cloud_id = provider.request(Instance("i3", {}))
    transport.nodes[cloud_id]["state"] = "ERROR"
    assert provider.poll() == {cloud_id: "failed"}
    assert cloud_id not in transport.nodes


def test_reconciler_drives_gce_slice_lifecycle():
    """Acceptance criteria: the autoscaler_v2 reconciler drives
    GceTpuNodeProvider against a stubbed transport — create, label, ray
    join, then terminate — atomically for a multi-host slice."""
    transport = FakeTransport()
    gcs = FakeGcs()
    provider = _gce_provider(transport, gcs=gcs)
    im = InstanceManager(provider, shape={"accelerator_type": "v5litepod-16"})
    im.set_target(1)
    im.reconcile()
    assert im.counts() == {"REQUESTED": 1}
    (cloud_id,) = transport.nodes
    assert transport.nodes[cloud_id]["labels"]["ray-tpu-cluster"] == "ray-tpu"

    transport.make_ready(cloud_id, hosts=4)
    im.reconcile()
    assert im.counts() == {ALLOCATED: 1}
    for i in range(4):
        gcs.join(f"host{i}", cloud_id, worker_index=i)
    im.reconcile()
    assert im.counts() == {RAY_RUNNING: 1}
    inst = next(iter(im.instances.values()))
    assert inst.node_id == "host0"

    im.set_target(0)
    im.reconcile()
    im.reconcile()
    assert cloud_id not in transport.nodes  # slice deleted, atomically
    assert im.counts() == {"TERMINATED": 1}


def test_gce_terminate_of_gone_node_is_success():
    """An already-deleted node (preempted / torn down by a poll round) must
    not wedge the instance in TERMINATING: DELETE->404 is success."""
    transport = FakeTransport()
    provider = _gce_provider(transport)
    cloud_id = provider.request(Instance("i4", {}))
    transport.nodes.pop(cloud_id)  # deleted out-of-band
    provider.terminate(cloud_id)  # must not raise
    assert provider.poll() == {}  # and the id is no longer tracked


def test_gce_poll_follows_pagination():
    """A node on page 2 of the listing must not read as "gone" (reconcile
    would terminate a healthy slice over it)."""
    transport = FakeTransport()
    provider = _gce_provider(transport)
    # Unrelated nodes occupy page 1.
    for i in range(3):
        transport.nodes[f"other-{i}"] = {
            "name": f"projects/p/locations/z/nodes/other-{i}", "state": "READY",
        }
    cloud_id = provider.request(Instance("i5", {}))
    transport.make_ready(cloud_id, hosts=4)
    transport.page_size = 3  # our node falls onto page 2
    assert provider.poll() == {cloud_id: "running"}


def test_reconciler_retries_failed_gce_create():
    import time

    transport = FakeTransport()
    transport.fail_creates = 1  # first POST rejected (quota)
    provider = _gce_provider(transport)
    im = InstanceManager(provider, retry_backoff_s=0.01, max_retries=2)
    im.set_target(1)
    im.reconcile()
    assert im.counts() == {"ALLOCATION_FAILED": 1}
    time.sleep(0.05)
    im.reconcile()
    assert im.counts() == {"REQUESTED": 1}
    assert len(transport.nodes) == 1


def test_raylet_clamps_tpu_total_to_visible_chips():
    """A raylet started inside a chip lease (inherited TPU_VISIBLE_CHIPS)
    must advertise only the chips it can actually sublease — otherwise a
    bundle could reserve more TPU than there are leasable chips, skip the
    chip lease, and its workers would see sibling raylets' chips."""
    import os

    import ray_tpu as rtpu
    from ray_tpu.core import runtime_base
    from ray_tpu.core.cluster_runtime import Cluster

    rtpu.shutdown()
    saved = os.environ.get("TPU_VISIBLE_CHIPS")
    cluster = Cluster(num_cpus=1, num_workers=0)
    rt = cluster.runtime()
    runtime_base.set_runtime(rt)
    try:
        os.environ["TPU_VISIBLE_CHIPS"] = "0,1"  # inherited by the raylet
        nid = cluster.add_node(num_cpus=1, resources={"TPU": 4.0})
        node = {n["NodeID"]: n for n in rt._gcs.call("list_nodes")}[nid]
        assert node["Resources"]["TPU"] == 2.0
    finally:
        if saved is None:
            os.environ.pop("TPU_VISIBLE_CHIPS", None)
        else:
            os.environ["TPU_VISIBLE_CHIPS"] = saved
        rt.shutdown()
        cluster.shutdown()
