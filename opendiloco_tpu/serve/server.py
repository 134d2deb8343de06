"""Socket front-end for the serve plane: HTTP + JSONL on one port.

Same minimal-socket idiom as ``obs/prom.py`` — a daemon accept loop, one
handler thread per connection, no framework. Both protocols carry the
same JSON request shape::

    {"prompt": [1, 2, 3], "max_new_tokens": 16, "eos_id": null}

- HTTP: ``POST /generate`` with that JSON body; ``GET /healthz`` and
  ``GET /stats`` return scheduler/engine status. Metrics are NOT here —
  they ride the existing obs Prometheus endpoint (one registry per
  process, see obs/prom.py).
- JSONL: any connection whose first bytes are not an HTTP verb is
  treated as a newline-delimited JSON stream; each line gets a response
  line (pipelined in order). An optional ``"id"`` field is echoed back.

Port collisions (e.g. serve.port accidentally equal to
``ODTP_OBS_PROM_PORT``) downgrade to an ephemeral port with a warning
instead of crashing the training process — the bound port is always
``ServeServer.port``.
"""
from __future__ import annotations

import json
import logging
import select
import socket
import threading
import time
from typing import Callable, Optional, Union

from opendiloco_tpu.obs import reqtrace
from opendiloco_tpu.serve.scheduler import ContinuousBatcher

log = logging.getLogger(__name__)

_HTTP_VERBS = (b"GET ", b"POST", b"PUT ", b"HEAD", b"DELE", b"OPTI", b"PATC")

# scheduler rejects that are the server's load, not the request's fault:
# answered as structured 503 + Retry-After so clients back off cleanly
_OVERLOAD_ERRORS = ("queue full", "deadline exceeded")


def bind_with_fallback(
    host: str, port: int, what: str, retry_s: float = 0.0
) -> socket.socket:
    """Bind (host, port), falling back to an ephemeral port when the
    requested one is taken — a shared-process serving plane must never
    take down training over a port clash.

    ``retry_s`` keeps retrying the EXPLICIT port with bounded backoff
    before falling back: a replica respawned at its old address races the
    dying process's listener teardown, and an ephemeral fallback there
    would strand the router/manager dialing the address they know."""
    sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    deadline = time.monotonic() + max(0.0, retry_s)
    pause = 0.05
    while True:
        try:
            sock.bind((host, port))
            return sock
        except OSError as e:
            if port == 0:
                sock.close()
                raise
            if time.monotonic() + pause <= deadline:
                time.sleep(pause)
                pause = min(pause * 2, 0.5)
                continue
            log.warning(
                "%s port %d unavailable (%s); falling back to an "
                "ephemeral port",
                what,
                port,
                e,
            )
            sock.bind((host, 0))
            return sock


class ServeServer:
    def __init__(
        self,
        batcher: ContinuousBatcher,
        *,
        host: str = "127.0.0.1",
        port: int = 0,
        request_timeout: float = 300.0,
        identity: Optional[Union[dict, Callable[[], dict]]] = None,
        bind_retry_s: float = 0.0,
    ):
        self.batcher = batcher
        self.request_timeout = float(request_timeout)
        self.rejected_total = 0  # structured 503 rejects served
        # who this serving process is (worker/replica id, staleness, ...):
        # a dict, or a callable re-evaluated per request so dynamic fields
        # like staleness stay live. Folded into /healthz and /stats so a
        # fleet router (or odtp_top) can tell replicas apart.
        self._identity = identity
        self._sock = bind_with_fallback(host, port, "serve", bind_retry_s)
        self._sock.listen(32)
        self.host = host
        self.port = self._sock.getsockname()[1]
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._accept_loop, name="odtp-serve-http", daemon=True
        )
        self._thread.start()

    # -- accept / dispatch -------------------------------------------------

    def _accept_loop(self) -> None:
        while not self._stop.is_set():
            try:
                conn, _ = self._sock.accept()
            except OSError:
                return
            threading.Thread(
                target=self._handle, args=(conn,), daemon=True
            ).start()

    def _handle(self, conn: socket.socket) -> None:
        try:
            conn.settimeout(self.request_timeout)
            head = conn.recv(4096)
            if not head:
                return
            if head[:4].ljust(4) in _HTTP_VERBS or head[:5] == b"PATCH":
                self._handle_http(conn, head)
            else:
                self._handle_jsonl(conn, head)
        except OSError:
            pass
        finally:
            try:
                conn.close()
            except OSError:
                pass

    # -- identity ------------------------------------------------------------

    def identity(self) -> dict:
        ident = self._identity
        if ident is None:
            return {}
        return dict(ident() if callable(ident) else ident)

    # -- one generation ----------------------------------------------------

    @staticmethod
    def _disconnected(conn: socket.socket) -> bool:
        """True when the peer closed the connection (EOF is readable)."""
        try:
            readable, _, _ = select.select([conn], [], [], 0)
            if not readable:
                return False
            return conn.recv(1, socket.MSG_PEEK) == b""
        except (OSError, ValueError):
            return True

    def _retry_after_s(self) -> float:
        """Backpressure hint for structured 503 rejects: the scheduler's
        current queue-drain estimate, clamped to something a client can
        reasonably sleep on."""
        return round(min(30.0, max(0.1, self.batcher.estimate_wait_s())), 3)

    def _generate(
        self, payload: dict, conn: Optional[socket.socket] = None
    ) -> Optional[dict]:
        deadline_ms = payload.get("deadline_ms")
        # trace context: adopt one propagated from the router, else mint
        # at this edge (standalone serve plane). Absent field = old peer
        # or untraced request — both identical, nothing to version-check.
        trace_ctx = None
        rt = reqtrace.ring()
        if rt is not None:
            trace_ctx = reqtrace.ctx_of(payload)
            if trace_ctx is None:
                trace_ctx = rt.mint(at="server", req_id=payload.get("id"))
        req = self.batcher.submit(
            payload.get("prompt") or [],
            max_new_tokens=int(payload.get("max_new_tokens", 16)),
            eos_id=payload.get("eos_id"),
            priority=int(payload.get("priority", 0)),
            deadline_ms=None if deadline_ms is None else float(deadline_ms),
            trace=trace_ctx,
        )
        # wait in slices, watching the client socket: a disconnect
        # mid-generation retires the slot immediately instead of decoding
        # the remaining tokens into a dead socket (None = nobody to answer)
        deadline = time.monotonic() + self.request_timeout
        while not req.wait(0.05):
            if conn is not None and self._disconnected(conn):
                req.cancel()
                return None
            if time.monotonic() >= deadline:
                req.cancel()
                return {"error": "timeout", "id": payload.get("id")}
        out = {
            "tokens": req.tokens,
            "epoch": req.epoch,
            "latency_ms": None
            if req.latency_s is None
            else round(req.latency_s * 1e3, 3),
        }
        if req.error is not None:
            out["error"] = req.error
            if req.error in _OVERLOAD_ERRORS:
                # structured backpressure: the client learns when to come
                # back instead of watching its connection error out
                out["retry_after_s"] = self._retry_after_s()
                self.rejected_total += 1
        if payload.get("id") is not None:
            out["id"] = payload["id"]
        return out

    # -- HTTP --------------------------------------------------------------

    def _handle_http(self, conn: socket.socket, head: bytes) -> None:
        while b"\r\n\r\n" not in head and len(head) < 65536:
            chunk = conn.recv(4096)
            if not chunk:
                break
            head += chunk
        header, _, body = head.partition(b"\r\n\r\n")
        lines = header.split(b"\r\n")
        method, path = (lines[0].split(b" ") + [b"", b""])[:2]
        clen = 0
        for ln in lines[1:]:
            if ln.lower().startswith(b"content-length:"):
                clen = int(ln.split(b":", 1)[1].strip() or 0)
        while len(body) < clen:
            chunk = conn.recv(65536)
            if not chunk:
                break
            body += chunk

        if method == b"POST" and path.startswith(b"/generate"):
            try:
                payload = json.loads(body.decode() or "{}")
            except (ValueError, UnicodeDecodeError):
                self._respond(conn, 400, {"error": "malformed JSON body"})
                return
            out = self._generate(payload, conn)
            if out is not None:
                if out.get("error") in _OVERLOAD_ERRORS:
                    self._respond(
                        conn,
                        503,
                        out,
                        headers={"Retry-After": str(out["retry_after_s"])},
                    )
                else:
                    self._respond(conn, 400 if "error" in out else 200, out)
        elif method == b"GET" and path.startswith(b"/healthz"):
            self._respond(
                conn,
                200,
                {
                    "ok": self.batcher.loop_error is None,
                    "weights_epoch": self.batcher.engine.weights_epoch,
                    "staleness": self.batcher.engine.staleness(),
                    "free_slots": self.batcher.slots.num_free,
                    # where and how decode really runs, as resolved
                    "platform": self.batcher.engine.device.platform,
                    "device_kind": self.batcher.engine.device.device_kind,
                    "decode_kernel": self.batcher.engine.decode_kernel,
                    # cold-tier load rides health so pollers (router
                    # probe, odtp_top) see paging pressure without /stats
                    **(
                        {
                            "tier_occupancy": round(
                                self.batcher.kv_tier.occupancy(), 4
                            ),
                            "tier_paused": self.batcher.kv_tier.paused_count,
                        }
                        if self.batcher.kv_tier is not None
                        else {}
                    ),
                    **self.identity(),
                },
            )
        elif method == b"GET" and path.startswith(b"/stats"):
            stats = self.batcher.stats()
            stats["rejected_total"] = self.rejected_total
            ident = self.identity()
            if ident:
                stats["identity"] = ident
            self._respond(conn, 200, stats)
        else:
            self._respond(conn, 404, {"error": "unknown route"})

    def _respond(
        self,
        conn: socket.socket,
        status: int,
        obj: dict,
        headers: Optional[dict] = None,
    ) -> None:
        body = (json.dumps(obj) + "\n").encode()
        reason = {
            200: "OK",
            400: "Bad Request",
            404: "Not Found",
            503: "Service Unavailable",
        }.get(status, "Error")
        extra = "".join(f"{k}: {v}\r\n" for k, v in (headers or {}).items())
        head = (
            f"HTTP/1.0 {status} {reason}\r\n"
            "Content-Type: application/json\r\n"
            f"{extra}"
            f"Content-Length: {len(body)}\r\n\r\n"
        ).encode()
        conn.sendall(head + body)

    # -- JSONL -------------------------------------------------------------

    def _handle_jsonl(self, conn: socket.socket, buf: bytes) -> None:
        while True:
            while b"\n" in buf:
                line, _, buf = buf.partition(b"\n")
                line = line.strip()
                if not line:
                    continue
                try:
                    payload = json.loads(line.decode())
                except (ValueError, UnicodeDecodeError):
                    out = {"error": "malformed JSON line"}
                else:
                    out = self._generate(payload, conn)
                    if out is None:  # client disconnected mid-generation
                        return
                conn.sendall((json.dumps(out) + "\n").encode())
            chunk = conn.recv(65536)
            if not chunk:
                return
            buf += chunk

    def stop(self) -> None:
        self._stop.set()
        try:
            self._sock.close()
        except OSError:
            pass
        self._thread.join(timeout=2.0)
