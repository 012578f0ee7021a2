"""A plain NDJSON client for the daemon's ctrl port (stdlib only).

One request per line, `{"id", "method", "params"}`; the reply line
carries the same id and `result` or `error`.  Query clients use this in
their own process, which never imports JAX or the program.
"""

from __future__ import annotations

import json
import socket


class WireError(RuntimeError):
    pass


class Client:
    def __init__(self, port: int, host: str = "::1", timeout_s: float = 600.0) -> None:
        self._sock = socket.create_connection((host, port), timeout=timeout_s)
        self._rfile = self._sock.makefile("rb")
        self._next = 0

    def call(self, method: str, **params):
        self._next += 1
        msg = {"id": self._next, "method": method, "params": params}
        self._sock.sendall(json.dumps(msg).encode() + b"\n")
        while True:
            line = self._rfile.readline()
            if not line:
                raise WireError("ctrl server closed the connection")
            reply = json.loads(line)
            if reply.get("id") != self._next:
                continue
            if "error" in reply:
                raise WireError(str(reply["error"]))
            return reply.get("result")

    def close(self) -> None:
        self._rfile.close()
        self._sock.close()
