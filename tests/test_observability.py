"""State API, task timeline, and dashboard-lite tests."""

import json
import time

import numpy as np
import pytest

import ray_tpu
from ray_tpu.util import state


@pytest.fixture
def obs_cluster():
    ray_tpu.init(num_cpus=4, object_store_memory=128 * 1024 * 1024)
    yield
    ray_tpu.shutdown()


def _wait_events(n, timeout=15):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        events = state.list_tasks()
        if len(events) >= n:
            return events
        time.sleep(0.3)
    raise AssertionError(f"only {len(state.list_tasks())} events")


def test_task_events_and_timeline(obs_cluster, tmp_path):
    @ray_tpu.remote
    def work(i):
        time.sleep(0.05)
        return i

    ray_tpu.get([work.remote(i) for i in range(5)], timeout=60)
    events = _wait_events(5)
    assert all(e["end"] >= e["start"] for e in events)
    assert any(e["name"] == "work" for e in events)

    out = str(tmp_path / "trace.json")
    trace = ray_tpu.timeline(out)
    assert len(trace) >= 5
    loaded = json.load(open(out))
    assert loaded[0]["ph"] == "X" and loaded[0]["dur"] >= 0

    summary = state.summarize_tasks()
    assert summary["by_func_name"].get("work", 0) >= 5


def test_list_actors_and_nodes(obs_cluster):
    @ray_tpu.remote
    class A:
        def ping(self):
            return 1

    a = A.remote()
    ray_tpu.get(a.ping.remote(), timeout=30)
    actors = state.list_actors()
    assert any(x["state"] == "ALIVE" for x in actors)
    nodes = state.list_nodes()
    assert len(nodes) == 1 and nodes[0]["alive"]
    assert state.summarize_actors()["total"] >= 1


def test_dashboard_endpoints(obs_cluster):
    import requests

    from ray_tpu.dashboard import start_dashboard

    @ray_tpu.remote
    def t():
        return 1

    ray_tpu.get([t.remote() for _ in range(3)], timeout=30)
    _wait_events(3)
    url = start_dashboard(port=18265)
    # Prometheus file-based service discovery written into the session dir
    import glob as _glob
    import time as _time

    deadline = _time.time() + 10
    sd_files = []
    while _time.time() < deadline and not sd_files:
        sd_files = _glob.glob(
            "/tmp/raytpu/s_*/prom_metrics_service_discovery.json")
        _time.sleep(0.2)
    assert sd_files, "prometheus service-discovery file not written"
    import json as _json

    # stale session dirs may linger in /tmp: any file with our target OK
    targets = [t for f in sd_files for e in _json.load(open(f))
               for t in e.get("targets", [])]
    assert "127.0.0.1:18265" in targets, targets

    nodes = requests.get(f"{url}/api/nodes", timeout=30).json()
    assert len(nodes) == 1
    summary = requests.get(f"{url}/api/summary", timeout=30).json()
    assert summary["tasks"]["total"] >= 3
    metrics = requests.get(f"{url}/metrics", timeout=30).text
    assert "raytpu_nodes 1" in metrics
    assert "raytpu_tasks_finished_total" in metrics
    assert 'raytpu_resource_total{node=' in metrics


def test_profile_device_captures_xplane(tmp_path):
    """profile_device wraps jax.profiler: a device trace lands in
    TensorBoard/XProf format next to the task timeline (SURVEY 5.1
    device-trace capture)."""
    import glob
    import os

    import jax.numpy as jnp

    from ray_tpu.util.state import profile_device

    from ray_tpu._private.telemetry import Phases

    d = str(tmp_path / "trace")
    with profile_device(d):
        with Phases("engine").phase("emit"):
            jnp.sum(jnp.arange(1000.0)).block_until_ready()
    path, = glob.glob(os.path.join(d, "**", "*.xplane.pb"),
                      recursive=True)
    # an operator's capture is readable: the Python tracer is off (it
    # writes an event per call), and the program's own spans are there
    from jax.profiler import ProfileData

    host = [e.name for plane in ProfileData.from_file(path).planes
            if plane.name == "/host:CPU"
            for line in plane.lines for e in line.events]
    assert "raytpu.engine.emit" in host
    assert not any(name.startswith("$") for name in host), \
        "python tracer events in the capture"


def test_profile_device_raises_when_profiler_fails(tmp_path, monkeypatch):
    """A profiler that will not start is an error, not a silent run
    without a trace."""
    import jax

    from ray_tpu.util.state import profile_device

    def boom(*a, **k):
        raise RuntimeError("no profiler on this backend")

    monkeypatch.setattr(jax.profiler, "start_trace", boom)
    with pytest.raises(RuntimeError, match="no profiler"):
        with profile_device(str(tmp_path / "x")):
            pass
