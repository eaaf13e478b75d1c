"""GCS continuous persistence: WAL between snapshots (reference analog:
the Redis-backed store's continuous durability,
src/ray/gcs/store_client/redis_store_client.h:28).  Every acknowledged
mutation must survive a hard kill, snapshot or not."""

import asyncio
import os
import signal
import subprocess
import sys
import time

import pytest

from ray_tpu._private.gcs import GcsServer, _WAL


def _run(coro):
    return asyncio.get_event_loop_policy().new_event_loop() \
        .run_until_complete(coro)


def _mk(persist):
    return GcsServer(persist_path=str(persist))


def test_wal_survives_crash_before_any_snapshot(tmp_path):
    persist = tmp_path / "gcs.pkl"
    g = _mk(persist)

    async def burst():
        for i in range(50):
            await g.rpc_kv_put(None, {"key": f"k{i}",
                                      "value": f"v{i}".encode()})
        await g.rpc_kv_del(None, {"key": "k0"})
        await g.rpc_job_register(None, {})

    _run(burst())
    # crash: no snapshot was ever written (monitor loop never ran)
    assert not os.path.exists(persist)

    g2 = _mk(persist)
    g2._restore()
    assert g2.kv.get("k49") == b"v49"
    assert "k0" not in g2.kv
    assert g2._job_counter == 1


def test_wal_truncated_after_snapshot_and_replay_idempotent(tmp_path):
    persist = tmp_path / "gcs.pkl"
    g = _mk(persist)
    _run(g.rpc_kv_put(None, {"key": "a", "value": b"1"}))
    # snapshot flow as the monitor loop runs it
    state = g._capture_state()
    g._wal.rotate()
    g._write_snapshot(state)
    g._wal.commit_rotation()
    _run(g.rpc_kv_put(None, {"key": "b", "value": b"2"}))

    g2 = _mk(persist)
    g2._restore()
    assert g2.kv == {"a": b"1", "b": b"2"}


def test_crash_between_rotate_and_snapshot_write(tmp_path):
    """The nastiest window: WAL rotated (records in .old), snapshot not
    yet written.  Replay must fold .old + current."""
    persist = tmp_path / "gcs.pkl"
    g = _mk(persist)
    _run(g.rpc_kv_put(None, {"key": "early", "value": b"x"}))
    g._capture_state()
    g._wal.rotate()          # crash here: snapshot never written
    _run(g.rpc_kv_put(None, {"key": "late", "value": b"y"}))

    g2 = _mk(persist)
    g2._restore()
    assert g2.kv.get("early") == b"x"
    assert g2.kv.get("late") == b"y"


def test_snapshot_write_failure_splices_wal_back(tmp_path):
    persist = tmp_path / "gcs.pkl"
    g = _mk(persist)
    _run(g.rpc_kv_put(None, {"key": "a", "value": b"1"}))
    g._capture_state()
    g._wal.rotate()
    _run(g.rpc_kv_put(None, {"key": "b", "value": b"2"}))
    g._wal.abort_rotation()  # snapshot write "failed"

    g2 = _mk(persist)
    g2._restore()
    assert g2.kv == {"a": b"1", "b": b"2"}


def test_torn_tail_record_dropped(tmp_path):
    persist = tmp_path / "gcs.pkl"
    g = _mk(persist)
    _run(g.rpc_kv_put(None, {"key": "whole", "value": b"1"}))
    # simulate a crash mid-append: chop the last record in half
    wal = str(persist) + ".wal"
    data = open(wal, "rb").read()
    open(wal, "wb").write(data[:len(data) - 3])

    g2 = _mk(persist)
    g2._restore()  # must not raise; the torn record is simply dropped
    assert "whole" not in g2.kv or g2.kv.get("whole") == b"1"


def test_detached_actor_and_pg_records(tmp_path):
    persist = tmp_path / "gcs.pkl"
    g = _mk(persist)

    # zero registered nodes: registration queues (cluster forming) and
    # the REGISTRATION record must survive a crash
    async def ops2():
        await g.rpc_actor_register(None, {
            "actor_id": b"\x02" * 12,
            "spec": {"resources": {"CPU": 1.0}, "fid": b"f"},
            "name": "det2", "max_restarts": 0,
            "lifetime": "detached"})
        await g.rpc_pg_create(None, {
            "pg_id": b"\x03" * 12,
            "bundles": [{"CPU": 1.0}], "strategy": "PACK",
            "name": "mypg"})

    _run(ops2())
    g2 = _mk(persist)
    g2._restore()
    assert g2.named_actors.get("det2") == b"\x02" * 12
    assert g2.named_pgs.get("mypg") == b"\x03" * 12


_KILL_SCRIPT = r"""
import os, sys, time
import ray_tpu
from ray_tpu._private import worker_context

persist = sys.argv[1]
ray_tpu.init(num_cpus=2, object_store_memory=128 * 1024 * 1024,
             _system_config={"gcs_persist_path": persist})
cw = worker_context.core_worker()
for i in range(200):
    cw.kv_put(f"burst:{i}", str(i).encode())
print("BURST_DONE", flush=True)
time.sleep(60)  # parent SIGKILLs us mid-life, snapshot tick or not
"""


def test_hard_kill_mid_burst_loses_nothing(tmp_path):
    """End-to-end: a head process acknowledges 200 kv writes and is
    SIGKILLed; the restarted head must see every one of them."""
    persist = str(tmp_path / "gcs.pkl")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    repo_root = os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))
    env["PYTHONPATH"] = repo_root + os.pathsep + env.get("PYTHONPATH",
                                                         "")
    script = tmp_path / "burst.py"
    script.write_text(_KILL_SCRIPT)
    proc = subprocess.Popen([sys.executable, str(script), persist],
                            stdout=subprocess.PIPE, env=env, text=True)
    deadline = time.monotonic() + 120
    line = ""
    while time.monotonic() < deadline:
        line = proc.stdout.readline()
        if "BURST_DONE" in line:
            break
    assert "BURST_DONE" in line, "burst process never finished writes"
    os.kill(proc.pid, signal.SIGKILL)
    proc.wait(timeout=30)

    g = GcsServer(persist_path=persist)
    g._restore()
    missing = [i for i in range(200)
               if g.kv.get(f"burst:{i}") != str(i).encode()]
    assert not missing, f"lost {len(missing)} acknowledged writes"
