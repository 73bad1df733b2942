"""The embedded entry: statements go to `SnappySession.sql` in this
process. With the mix's `durable` the session has a `data_dir`, and the
bulk load is journaled like every other write."""

import os
import shutil
import tempfile

FRONT = BACK = "session"    # the kinds of trace a statement leaves


class Engine:
    def __init__(self, mix: dict):
        from snappydata_tpu import SnappySession
        from snappydata_tpu.catalog import Catalog

        self.tmp = None
        if mix.get("durable"):
            # TMPDIR is the driver's own for each side; removed at the end
            self.tmp = tempfile.mkdtemp(prefix="snappybench-")
            self.session = SnappySession(catalog=Catalog(),
                                         data_dir=self.tmp)
        else:
            self.session = SnappySession(catalog=Catalog())

    def create(self, ddl: str) -> None:
        self.session.sql(ddl)

    def load(self, table: str, cols: dict) -> None:
        self.session.insert_arrays(table, list(cols.values()))

    def serve(self) -> None:
        pass

    def query(self, sql: str, params: list) -> list:
        return [tuple(r) for r in self.session.sql(sql, params).rows()]

    def insert(self, table: str, cols: dict) -> None:
        self.session.insert_arrays(table, list(cols.values()))

    def execute(self, sql: str, params: list) -> None:
        self.session.sql(sql, params)

    def disk_bytes(self) -> int:
        """What the session's data_dir holds now (the WAL and whatever a
        checkpoint wrote): what a run writes to disk, less what it has
        deleted."""
        total = 0
        for d, _, files in os.walk(self.tmp or ""):
            total += sum(os.path.getsize(os.path.join(d, f))
                         for f in files if os.path.exists(
                             os.path.join(d, f)))
        return total

    def close(self) -> None:
        self.session.stop()
        if self.tmp:
            shutil.rmtree(self.tmp, ignore_errors=True)
