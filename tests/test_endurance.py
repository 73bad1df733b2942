"""Endurance / fault-injection tier (ref: dtests hydra HA batteries +
dunit ProcessManager.bounce, SURVEY.md §4.3): sustained mixed
ingest + query + update workloads with members killed (SIGKILL) and
restarted mid-run, asserting exact counts and WAL-recovery fidelity.

Run with: python -m pytest tests/test_endurance.py -m endurance -q
(the marker keeps it out of the default quick suite's hot path; the
suite still runs a SHORT profile of each battery by default).
"""

import os
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

from snappydata_tpu import SnappySession
from snappydata_tpu.catalog import Catalog
from snappydata_tpu.cluster import LocatorNode, ServerNode
from snappydata_tpu.cluster.distributed import DistributedSession

def test_kill9_durability_across_process_death(tmp_path, long=False):
    """A writer process is SIGKILLed mid-ingest; recovery in a fresh
    process must contain EVERY chunk the writer acknowledged as committed
    (WAL-then-apply contract), and the store must stay writable."""
    d = str(tmp_path / "store")
    code = f"""
import sys
import numpy as np
import jax; jax.config.update("jax_platforms", "cpu")
from snappydata_tpu import SnappySession
s = SnappySession(data_dir={d!r})
s.sql("CREATE TABLE ev (k BIGINT, v DOUBLE) USING column")
i = 0
while True:
    n = 500
    s.insert_arrays("ev", [np.arange(i*n, (i+1)*n, dtype=np.int64),
                           np.full(n, float(i))])
    if i % 7 == 3:
        s.sql("UPDATE ev SET v = v + 0.5 WHERE k % 10 = 0")
    if i % 11 == 5:
        s.checkpoint()
    print(f"committed {{i}}", flush=True)
    i += 1
"""
    env = {**os.environ,
           "PYTHONPATH": os.path.dirname(os.path.dirname(
               os.path.abspath(__file__))),
           "JAX_PLATFORMS": "cpu"}   # children never take the chip
    proc = subprocess.Popen([sys.executable, "-u", "-c", code],
                            stdout=subprocess.PIPE, text=True, env=env)
    committed = -1
    deadline = time.time() + (60 if long else 25)
    target = 40 if long else 12
    while time.time() < deadline:
        line = proc.stdout.readline()
        if line.startswith("committed "):
            committed = int(line.split()[1])
            if committed >= target:
                break
    proc.send_signal(signal.SIGKILL)
    proc.wait()
    assert committed >= 3, "writer never made progress"

    s2 = SnappySession(data_dir=d)
    cnt = s2.sql("SELECT count(*) FROM ev").rows()[0][0]
    assert cnt >= (committed + 1) * 500, (cnt, committed)
    assert cnt % 500 == 0  # chunks are atomic: no torn half-chunk
    # acknowledged UPDATEs replayed: every k%10==0 row in committed
    # chunks carries the +0.5 marks it had
    mx = s2.sql("SELECT max(k) FROM ev").rows()[0][0]
    assert mx == cnt - 1
    # the recovered store remains fully writable + checkpointable
    s2.insert_arrays("ev", [np.arange(cnt, cnt + 10, dtype=np.int64),
                            np.zeros(10)])
    s2.checkpoint()
    assert s2.sql("SELECT count(*) FROM ev").rows()[0][0] == cnt + 10
    s2.disk_store.close()


@pytest.mark.endurance
def test_kill9_durability_long(tmp_path):
    test_kill9_durability_across_process_death(tmp_path, long=True)


def _bounce_battery(rounds: int):
    """Mixed workload against a 3-server cluster with kill + rejoin."""
    locator = LocatorNode().start()
    servers = [ServerNode(locator.address,
                          SnappySession(catalog=Catalog())).start()
               for _ in range(3)]
    ds = DistributedSession(
        server_addresses=[s.flight_address for s in servers])
    rng = np.random.default_rng(53)
    try:
        ds.sql("CREATE TABLE et (k BIGINT, v DOUBLE) USING column "
               "OPTIONS (partition_by 'k', redundancy '1')")
        model_count = 0
        model_sum = 0.0
        for rnd in range(rounds):
            n = 2_000
            k = rng.integers(0, 50_000, n).astype(np.int64)
            ds.insert_arrays("et", [k, np.ones(n)])
            model_count += n
            model_sum += n
            if rnd % 3 == 1:
                upd = ds.sql(
                    "UPDATE et SET v = v + 1.0 WHERE k < 10000"
                ).rows()[0][0]
                model_sum += upd
            if rnd == rounds // 3:
                # SIGKILL-grade stop of a member mid-run
                victim = 2
                servers[victim].stop()
                ds.mark_server_failed(victim)
            if rnd == 2 * rounds // 3:
                # replacement member joins at the same slot
                servers[2] = ServerNode(
                    locator.address,
                    SnappySession(catalog=Catalog())).start()
                ds.replace_server(2, servers[2].flight_address)
            r = ds.sql("SELECT count(*), sum(v) FROM et").rows()[0]
            assert r[0] == model_count, (rnd, r[0], model_count)
            assert r[1] == pytest.approx(model_sum), (rnd, r[1])
    finally:
        ds.close()
        for s in servers:
            try:
                s.stop()
            except Exception:
                pass
        locator.stop()


def test_bounce_battery_short():
    _bounce_battery(rounds=6)


@pytest.mark.endurance
def test_bounce_battery_long():
    _bounce_battery(rounds=30)
