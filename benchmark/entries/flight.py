"""The served entry: a `SnappyFlightServer` on the session, started in
this process (one process owns the chip), and a `SnappyClient` that
every statement of the window goes through. The bulk load goes through
the session itself, journaled where the mix is `durable`."""

import threading

import embedded

FRONT, BACK = "client", "server"


class Engine(embedded.Engine):
    def __init__(self, mix: dict):
        super().__init__(mix)
        self.server = self.thread = self.client = None

    def serve(self) -> None:
        from snappydata_tpu.cluster import SnappyClient
        from snappydata_tpu.cluster.flight_server import SnappyFlightServer

        self.server = SnappyFlightServer(self.session, port=0)
        self.thread = threading.Thread(target=self.server.serve,
                                       daemon=True)
        self.thread.start()
        self.server.wait_ready()
        self.client = SnappyClient(
            address=f"127.0.0.1:{self.server.actual_port}")

    def query(self, sql: str, params: list) -> list:
        t = self.client.sql(sql, params=params)
        return [tuple(r) for r in zip(*(c.to_pylist() for c in t.columns))]

    def insert(self, table: str, cols: dict) -> None:
        self.client.insert(table, cols)

    def execute(self, sql: str, params: list) -> None:
        self.client.execute(sql, params)

    def close(self) -> None:
        if self.client is not None:
            self.client.close()
        if self.server is not None:
            self.server.shutdown()
            self.thread.join(timeout=30)
            if self.thread.is_alive():
                raise RuntimeError("the Flight server thread did not stop")
        super().close()
