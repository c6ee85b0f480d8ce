#!/usr/bin/env python3
"""The served cells' client: a closed loop of NDJSON connections to a
query server, in a process of its own that imports only the standard
library (no JAX, so it never touches the chip).

    python3 bench/serve_client.py      # driven by bench/paths/serve.py

Protocol, one JSON line each way on stdin and stdout:

1. stdin: ``{"host", "port", "connections", "docs": [wire line, ...],
   "keep": [index, ...], "reply_timeout_s"}``;
2. the client opens its connections and prints ``{"ready": true}``;
3. stdin: ``{"close": t}``, a ``time.monotonic()`` reading (one clock
   for every process of the machine);
4. each connection sends the next unsent document of ``docs``, in order,
   reads its reply, and sends the next, until ``t``; then it reads the
   reply it waits for and stops;
5. the client prints ``{"records": [...], "exhausted": bool}``: per
   document answered, ``[index, sent, received, ok, error kind, stats,
   solver iterations, solver edges, reply]``, the reply line as it came
   for the indices in ``keep`` (parsed by the caller, after the window)
   and ``null`` otherwise.

Every read has a deadline (``reply_timeout_s``): a reply that does not
come is recorded with ``received`` null, and the client still reports.
"""
from __future__ import annotations

import json
import socket
import sys
import threading
import time

_DECODE = json.JSONDecoder()
# replies longer than this are read field by field, not parsed whole
_WHOLE = 1 << 16


def _field(line: str, key: str, last: bool = False):
    """The JSON value of the first (or ``last``) ``"key": `` in ``line``."""
    tag = f'"{key}": '
    i = line.rfind(tag) if last else line.find(tag)
    return None if i < 0 else _DECODE.raw_decode(line, i + len(tag))[0]


def summary(line: str) -> tuple:
    """``(ok, error kind, stats, solver iters, solver edges)`` of one
    reply line; a long one (a full PageRank reply) is read only where
    those fields are."""
    if len(line) < _WHOLE:
        env = json.loads(line)
        engine = (env.get("result") or {}).get("engine_stats") or {}
        return (bool(env.get("ok")),
                (env.get("error") or {}).get("kind"), env.get("stats"),
                engine.get("solver_iters"), engine.get("solver_edges"))
    engine = _field(line, "engine_stats") or {}
    return (_field(line, "ok") is True, None, _field(line, "stats", True),
            engine.get("solver_iters"), engine.get("solver_edges"))


class Client:
    def __init__(self, cfg: dict) -> None:
        self.docs = cfg["docs"]
        self.keep = set(cfg["keep"])
        self.timeout = float(cfg["reply_timeout_s"])
        self.conns = [socket.create_connection((cfg["host"], cfg["port"]),
                                               timeout=self.timeout)
                      for _ in range(cfg["connections"])]
        self.next = 0
        self.exhausted = False
        self.lock = threading.Lock()
        self.records: list[list] = []

    def _take(self, close: float) -> int | None:
        with self.lock:
            if time.monotonic() >= close:
                return None
            if self.next >= len(self.docs):
                self.exhausted = True
                return None
            self.next += 1
            return self.next - 1

    def _loop(self, conn: socket.socket, close: float) -> None:
        rfile = conn.makefile("rb")
        while (i := self._take(close)) is not None:
            sent = time.monotonic()
            try:
                conn.sendall(self.docs[i].encode() + b"\n")
                raw = rfile.readline()
            except OSError:
                raw = b""
            got = time.monotonic()
            if not raw.endswith(b"\n"):
                with self.lock:
                    self.records.append([i, sent, None, False, "no reply",
                                         None, None, None, None])
                return
            line = raw.decode()
            rec = summary(line)
            with self.lock:
                self.records.append([i, sent, got, *rec,
                                     line if i in self.keep else None])

    def run(self, close: float) -> dict:
        threads = [threading.Thread(target=self._loop, args=(c, close),
                                    daemon=True) for c in self.conns]
        for t in threads:
            t.start()
        for t in threads:
            t.join(max(close - time.monotonic(), 0) + self.timeout + 5)
        for c in self.conns:
            c.close()
        with self.lock:
            return {"records": sorted(self.records),
                    "exhausted": self.exhausted,
                    "stuck": sum(t.is_alive() for t in threads)}


def main() -> int:
    client = Client(json.loads(sys.stdin.readline()))
    print(json.dumps({"ready": True}), flush=True)
    close = json.loads(sys.stdin.readline())["close"]
    print(json.dumps(client.run(close)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
