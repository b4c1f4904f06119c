"""Head WAL durability: mutations survive a hard kill between snapshots.

Reference analog: GCS fault tolerance via the Redis store
(``src/ray/gcs/store_client/redis_store_client.cc``) — per-mutation
durability, not snapshot-timer durability. The head appends durable-table
mutations (KV, jobs) to a generational WAL (``_private/wal.py``); restart
replays snapshot + WAL.
"""
import os
import signal
import time

import pytest

from tests.conftest import start_head


def test_wal_record_roundtrip_and_torn_tail(tmp_path):
    from ray_tpu._private.wal import WalWriter, replay_all, replay_file

    prefix = str(tmp_path / "head.wal")
    w = WalWriter(prefix)
    w.append({"op": "kv_put", "ns": "a", "key": "k1", "val": b"v1"})
    w.append({"op": "kv_del", "ns": "a", "key": "k0"})
    w.close()
    ops = list(replay_all(prefix))
    assert [o["op"] for o in ops] == ["kv_put", "kv_del"]
    assert ops[0]["val"] == b"v1"

    # torn tail: truncate mid-record — earlier records still replay
    path = prefix + ".00000000"
    size = os.path.getsize(path)
    with open(path, "ab") as f:
        f.write(b"\x40\x00\x00\x00\x99\x99\x99\x99partial")
    full = list(replay_file(path))
    assert len(full) == 2  # corrupt tail dropped, intact prefix kept


def test_wal_rotation_deletes_old_generations(tmp_path):
    from ray_tpu._private.wal import WalWriter, existing_generations, replay_all

    prefix = str(tmp_path / "head.wal")
    w = WalWriter(prefix)
    w.append({"op": "kv_put", "ns": "a", "key": "k", "val": b"1"})
    old = w.rotate()
    w.append({"op": "kv_put", "ns": "a", "key": "k2", "val": b"2"})
    assert existing_generations(prefix) == [0, 1]
    w.delete_through(old)
    assert existing_generations(prefix) == [1]
    assert [o["key"] for o in replay_all(prefix)] == ["k2"]
    w.close()


@pytest.mark.parametrize("clean", [False])
def test_head_kv_survives_hard_kill_via_wal(tmp_path, clean, monkeypatch):
    """SIGKILL the head BEFORE any snapshot tick (interval = 1h): restart
    must recover KV purely from the WAL."""
    state_file = str(tmp_path / "head_state.bin")
    # fixed token shared by both head incarnations and this client (the
    # test skips the 0600 address file that normally distributes it)
    monkeypatch.setenv("RT_AUTH_TOKEN", "waltest" * 4)
    head = ("--state-file", state_file, "--state-save-interval", "3600",
            "--no-address-file")
    from ray_tpu._private.sync_client import SyncHeadClient

    proc, info = start_head(*head)
    try:
        client = SyncHeadClient(info["address"])
        client.call("kv_put", {"ns": "user", "key": "alpha"},
                    frames=[b"value-1"])
        client.call("kv_put", {"ns": "user", "key": "beta"},
                    frames=[b"value-2"])
        client.call("kv_del", {"ns": "user", "key": "alpha"})
        # fsync is coalesced off-loop; give it a beat
        time.sleep(0.5)
        client.close()
    finally:
        proc.send_signal(signal.SIGKILL)  # crash: no shutdown snapshot
        proc.wait(timeout=10)

    assert not os.path.exists(state_file)  # no snapshot ever written
    proc, info = start_head(*head)
    try:
        client = SyncHeadClient(info["address"])
        h, frames = client.call("kv_get", {"ns": "user", "key": "beta"})
        assert h["found"] and frames[0] == b"value-2"
        h, _ = client.call("kv_get", {"ns": "user", "key": "alpha"})
        assert not h["found"]  # the delete replayed too
        client.close()
    finally:
        proc.terminate()
        proc.wait(timeout=10)
