"""Kafka source: exactly-once offset-range protocol, consumer lag,
SIGKILL durability, throughput floor (ref: DirectKafkaStreamSource.scala:
29-40 direct offset-range consumption; SnappySinkCallback.scala:196-216
exactly-once sink; BASELINE.md north-star 1M events/s Kafka→table)."""

import os
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

from snappydata_tpu import SnappySession
from snappydata_tpu.catalog import Catalog
from snappydata_tpu.streaming.kafka import (InProcessBroker, KafkaSource,
                                            OFFSETS_TABLE, register_broker)
from snappydata_tpu.streaming.query import StreamingQuery


def _consume_all(q):
    return q.process_available()


def _mk(table="kt", conflation=False, partitions=4):
    s = SnappySession(catalog=Catalog())
    s.sql(f"CREATE TABLE {table} (id BIGINT PRIMARY KEY, v DOUBLE) "
          f"USING row")
    broker = InProcessBroker(num_partitions=partitions)
    src = KafkaSource(s, "q1", broker, "events", ["id", "v"],
                      max_records_per_batch=1000)
    q = StreamingQuery(s, "q1", src, table)
    return s, broker, src, q


def test_basic_consumption_and_offsets():
    s, broker, src, q = _mk()
    broker.produce("events", [{"id": i, "v": float(i)} for i in range(2500)])
    _consume_all(q)
    assert s.sql("SELECT count(*) FROM kt").rows()[0][0] == 2500
    assert s.sql("SELECT sum(id) FROM kt").rows()[0][0] == \
        sum(range(2500))
    # lag drains to zero, then grows with new production
    assert src.lag() == 0
    broker.produce("events", [{"id": 9000 + i, "v": 0.0}
                              for i in range(10)])
    assert src.lag() == 10
    assert q.progress()["consumer_lag"] == 10
    s.stop()


def test_replay_same_ranges_after_crash_before_apply():
    """Crash point A: ranges logged, sink never applied. The restarted
    query must re-consume EXACTLY the logged ranges (no loss, no dup)."""
    s, broker, src, q = _mk()
    broker.produce("events", [{"id": i, "v": 1.0} for i in range(100)])
    batch_id = 0
    got = src.next_batch(batch_id)       # logs ranges durably
    assert got is not None
    # "crash": nothing applied. A fresh source over the same session
    # re-reads the log and returns the identical batch.
    src2 = KafkaSource(s, "q1", broker, "events", ["id", "v"],
                       max_records_per_batch=1000)
    # concurrent production between crash and restart must NOT leak into
    # the replayed batch
    broker.produce("events", [{"id": 500 + i, "v": 2.0}
                              for i in range(50)])
    got2 = src2.next_batch(batch_id)
    assert sorted(got2[0]["id"].tolist()) == sorted(got[0]["id"].tolist())
    q2 = StreamingQuery(s, "q1", src2, "kt")
    _consume_all(q2)
    assert s.sql("SELECT count(*) FROM kt").rows()[0][0] == 150
    s.stop()


def test_duplicate_batch_not_double_applied():
    """Crash point B: batch applied + state recorded, then the same batch
    id replays — the sink's exactly-once check drops it."""
    s, broker, src, q = _mk()
    broker.produce("events", [{"id": i, "v": 1.0} for i in range(40)])
    _consume_all(q)
    before = s.sql("SELECT count(*), sum(v) FROM kt").rows()[0]
    # replay an OLD batch id (ranges re-logged — equivalent to dying
    # before prune): strictly-older batches are dropped outright
    last = q.sink.last_batch_id()
    src._log_ranges(0, {p: [0, 10] for p in range(4)})
    cols, _ = src.next_batch(0)
    if 0 < last:
        assert q.sink.process_batch(0, cols) is False  # dropped
    # replay the LAST batch id: applied again as idempotent puts — the
    # keyed table state must not change (possible-duplicate contract)
    src._log_ranges(last, {p: [0, 10] for p in range(4)})
    cols2, _ = src.next_batch(last)
    q.sink.process_batch(last, cols2)
    after = s.sql("SELECT count(*), sum(v) FROM kt").rows()[0]
    assert after == before
    s.stop()


def test_offset_log_pruned_after_apply():
    s, broker, src, q = _mk()
    broker.produce("events", [{"id": i, "v": 1.0} for i in range(5000)])
    _consume_all(q)
    rows = s.sql(f"SELECT count(*) FROM {OFFSETS_TABLE} "
                 f"WHERE query_id = 'q1'").rows()[0][0]
    assert rows <= 1   # only the latest batch's ranges may remain
    s.stop()


def test_kafka_stream_ddl():
    s = SnappySession(catalog=Catalog())
    broker = InProcessBroker(num_partitions=2)
    register_broker("t1", broker)
    s.sql("CREATE STREAM TABLE clicks (id BIGINT, page STRING) "
          "USING kafka_stream OPTIONS (topic 'clicks', "
          "brokers 'inproc://t1', key_columns 'id', interval '0.01')")
    broker.produce("clicks", [{"id": i, "page": f"p{i % 3}"}
                              for i in range(300)])
    deadline = time.time() + 10
    while time.time() < deadline:
        if s.sql("SELECT count(*) FROM clicks").rows()[0][0] == 300:
            break
        time.sleep(0.05)
    assert s.sql("SELECT count(*) FROM clicks").rows()[0][0] == 300
    prog = [p for p in s.streaming_queries()
            if p["name"] == "stream_clicks"][0]
    assert prog["topic"] == "clicks"
    assert prog["consumer_lag"] == 0
    s.sql("DROP TABLE clicks")
    s.stop()


def test_throughput_floor():
    """Not the benchmark (bench.py measures the real number) — a floor
    that catches pathological slowness in the ingest path."""
    s, broker, src, q = _mk(partitions=8)
    n = 100_000
    src.max_records = 50_000
    broker.produce("events", [{"id": i, "v": 1.0} for i in range(n)])
    t0 = time.time()
    _consume_all(q)
    dt = time.time() - t0
    assert s.sql("SELECT count(*) FROM kt").rows()[0][0] == n
    assert n / dt > 5000, f"{n / dt:.0f} events/s"
    s.stop()


def test_kill9_exactly_once_across_process_death(tmp_path):
    """Consumer process is SIGKILLed mid-stream; the restarted consumer
    must land EVERY produced record exactly once (durable FileBroker +
    offset log + exactly-once sink)."""
    d = str(tmp_path / "store")
    bdir = str(tmp_path / "broker")
    from snappydata_tpu.streaming.kafka import FileBroker

    producer = FileBroker(bdir, num_partitions=4)
    total = 30_000
    chunk = 1000
    produced = 0
    code = f"""
import sys, time
import jax; jax.config.update("jax_platforms", "cpu")
from snappydata_tpu import SnappySession
s = SnappySession(data_dir={d!r})
s.sql("CREATE STREAM TABLE IF NOT EXISTS kt (id BIGINT, v DOUBLE) "
      "USING kafka_stream "
      "OPTIONS (topic 'events', brokers 'file://{bdir}', "
      "key_columns 'id', interval '0.01', maxRecordsPerBatch '2000')")
while True:
    n = s.sql("SELECT count(*) FROM kt").rows()[0][0]
    print(f"landed {{n}}", flush=True)
    time.sleep(0.1)
"""
    env = {**os.environ,
           "PYTHONPATH": os.path.dirname(os.path.dirname(
               os.path.abspath(__file__))),
           "JAX_PLATFORMS": "cpu"}   # children never take the chip

    def spawn():
        return subprocess.Popen([sys.executable, "-u", "-c", code],
                                stdout=subprocess.PIPE, text=True, env=env)

    proc = spawn()
    landed = 0
    deadline = time.time() + 90
    while time.time() < deadline and produced < total:
        producer.produce("events",
                         [{"id": produced + i, "v": 1.0}
                          for i in range(chunk)])
        produced += chunk
        line = proc.stdout.readline()
        if line.startswith("landed "):
            landed = int(line.split()[1])
            if landed >= total // 3 and produced >= total // 2:
                break
    assert landed > 0, "consumer never made progress"
    proc.send_signal(signal.SIGKILL)
    proc.wait()
    while produced < total:      # finish producing while consumer is dead
        producer.produce("events",
                         [{"id": produced + i, "v": 1.0}
                          for i in range(chunk)])
        produced += chunk

    proc = spawn()
    deadline = time.time() + 120
    final = 0
    while time.time() < deadline:
        line = proc.stdout.readline()
        if line.startswith("landed "):
            final = int(line.split()[1])
            if final >= total:
                break
    proc.send_signal(signal.SIGKILL)
    proc.wait()
    assert final == total, f"{final} != {total}"

    # independent verification: every id exactly once
    s2 = SnappySession(data_dir=d)
    cnt, dcnt, ssum = s2.sql(
        "SELECT count(*), count(DISTINCT id), sum(v) FROM kt").rows()[0]
    assert cnt == total and dcnt == total
    assert ssum == pytest.approx(float(total))
    s2.disk_store.close()
