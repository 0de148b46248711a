"""The load generator: a process of its own that never imports JAX.

One thread, one ``selectors`` loop: it opens a connection when a request
is due (open loop) or when its client's last request has ended (closed
loop), streams ``POST /generate`` and stamps each token line as it is
read. Times are on ``time.monotonic()``, which the server's process
shares, and every latency is taken from the due time, so a stall of the
server (or of this loop: see ``late``) lengthens what later requests
wait, as it does for users.

Protocol with the harness: argv[1] is the plan file; this process warms
the server up with the plan's warm-up requests, prints ``warm``, reads
one line ``go <t0>`` from stdin (the window's opening on the monotonic
clock), runs ramp, window and drain, writes the records to the plan's
``out`` and exits 0.
"""

from __future__ import annotations

import json
import selectors
import socket
import sys
import time

from benchmark.schedule import prompt_tokens


class _Conn:
    """One streamed request in flight."""

    __slots__ = ("req", "sock", "out", "buf", "head", "status", "rec",
                 "connected")

    def __init__(self, req, sock, body: bytes, port: int):
        self.req, self.sock = req, sock
        self.out = memoryview(
            (f"POST /generate HTTP/1.0\r\nHost: 127.0.0.1:{port}\r\n"
             "Content-Type: application/json\r\n"
             f"Content-Length: {len(body)}\r\n\r\n").encode() + body)
        self.buf = b""
        self.head = True
        self.status = 0
        self.connected = False
        self.rec = {"index": req["index"], "client": req["client"],
                    "prompt": req["prompt"], "n_new": req["n_new"],
                    "due": req["due"], "sent": None, "first": None,
                    "last": None, "tokens": [], "bursts": [],
                    "error": None}


class LoadGen:
    def __init__(self, port: int, vocab: int, seed: int):
        self.port, self.vocab, self.seed = port, vocab, seed
        self.sel = selectors.DefaultSelector()
        self.live: dict = {}
        self.records: list = []
        self.on_done = None  # closed loop: called with the finished conn
        self.stall_max = 0.0  # longest this loop went without a turn
        self._turn = None

    def body(self, req) -> bytes:
        tokens = prompt_tokens(self.seed, req["index"], req["prompt"],
                               self.vocab)
        return json.dumps({"tokens": [tokens], "n_new": req["n_new"],
                           "stream": True},
                          separators=(",", ":")).encode()

    def start(self, req, body: bytes, t0: float) -> None:
        sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        sock.setblocking(False)
        sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        conn = _Conn(req, sock, body, self.port)
        conn.rec["sent"] = time.monotonic() - t0
        sock.connect_ex(("127.0.0.1", self.port))
        self.sel.register(sock, selectors.EVENT_WRITE, conn)
        self.live[sock] = conn

    def _finish(self, conn, error=None) -> None:
        if conn.sock in self.live:
            del self.live[conn.sock]
            self.sel.unregister(conn.sock)
            conn.sock.close()
        rec = conn.rec
        if error is None and conn.status != 200:
            error = f"HTTP {conn.status}: {conn.buf[:200]!r}"
        if error is None and len(rec["tokens"]) != rec["n_new"]:
            error = (f"{len(rec['tokens'])} of {rec['n_new']} tokens: "
                     f"{conn.buf[-200:]!r}")
        rec["error"] = error
        self.records.append(rec)
        if self.on_done is not None:
            self.on_done(conn)

    def _lines(self, conn, now: float) -> None:
        rec = conn.rec
        *lines, conn.buf = conn.buf.split(b"\n")
        before = len(rec["tokens"])
        for line in lines:
            if b'"token"' in line and b'"done"' not in line:
                rec["tokens"].append(json.loads(line)["token"])
            elif b'"error"' in line:
                conn.buf = line  # reported by _finish as the cause
        if len(rec["tokens"]) > before:
            if rec["first"] is None:
                rec["first"] = now
            rec["last"] = now
            rec["bursts"].append((now, len(rec["tokens"]) - before))

    def pump(self, timeout: float, t0: float) -> None:
        began = time.monotonic()
        if self._turn is not None and began - t0 >= 0.0:
            self.stall_max = max(self.stall_max, began - self._turn)
        ready = self.sel.select(max(timeout, 0.0))
        self._turn = time.monotonic()  # waiting in select is no stall
        for key, events in ready:
            conn = key.data
            try:
                if events & selectors.EVENT_WRITE:
                    if not conn.connected:
                        err = conn.sock.getsockopt(socket.SOL_SOCKET,
                                                   socket.SO_ERROR)
                        if err:
                            raise OSError(err, "connect failed")
                        conn.connected = True
                    sent = conn.sock.send(conn.out)
                    conn.out = conn.out[sent:]
                    if not conn.out:
                        self.sel.modify(conn.sock, selectors.EVENT_READ,
                                        conn)
                    continue
                data = conn.sock.recv(1 << 16)
                now = time.monotonic() - t0
                if not data:
                    self._finish(conn)
                    continue
                conn.buf += data
                if conn.head:
                    if b"\r\n\r\n" not in conn.buf:
                        continue
                    head, conn.buf = conn.buf.split(b"\r\n\r\n", 1)
                    conn.status = int(head.split(None, 2)[1])
                    conn.head = False
                if conn.status == 200:
                    self._lines(conn, now)
            except BlockingIOError:
                continue
            except OSError as e:
                self._finish(conn, error=repr(e))

    def abandon(self, why: str) -> None:
        for conn in list(self.live.values()):
            self._finish(conn, error=why)


def warm_up(gen: LoadGen, requests: list) -> None:
    """Singles one after the other, then the timed few at once."""
    t0 = time.monotonic()
    timed = []
    for i, w in enumerate(requests):
        req = {"index": -1 - i, "client": -1, "due": 0.0,
               "prompt": w["prompt"], "n_new": w["n_new"]}
        if w["at"] is None:
            gen.start(req, gen.body(req), t0)
            while gen.live:
                gen.pump(0.5, t0)
        else:
            timed.append((w["at"], req))
    base = time.monotonic()
    while timed or gen.live:
        now = time.monotonic() - base
        while timed and timed[0][0] <= now:
            _, req = timed.pop(0)
            gen.start(req, gen.body(req), t0)
        gen.pump(min(0.05, timed[0][0] - now) if timed else 0.5, t0)
    bad = [r for r in gen.records if r["error"]]
    if bad:
        raise RuntimeError(f"warm-up request failed: {bad[0]['error']}")
    gen.records.clear()


def run_open(gen: LoadGen, plan: dict, t0: float) -> None:
    pending = sorted(plan["requests"], key=lambda r: r["due"])
    bodies = {r["index"]: gen.body(r) for r in pending}
    end = plan["seconds"] + plan["drain_s"]
    while pending or gen.live:
        now = time.monotonic() - t0
        while pending and pending[0]["due"] <= now:
            req = pending.pop(0)
            gen.start(req, bodies.pop(req["index"]), t0)
        if now > end:
            gen.abandon("not finished when the drain's cap ran out")
            break
        wait = min(0.05, pending[0]["due"] - now) if pending else 0.05
        gen.pump(wait, t0)


def run_closed(gen: LoadGen, plan: dict, t0: float) -> None:
    """Each client's next request goes out when its last one ends, until
    the window closes. What is in flight then is read for ``drain_s``
    more, so that each stream's next delivery after the window is seen
    (tokens are credited over the time since a stream's last delivery),
    then dropped (the server cancels the rows when the connections
    close) and recorded as cut."""
    queues: dict = {}
    for r in plan["requests"]:
        queues.setdefault(r["client"], []).append(r)
    first = sorted((q[0] for q in queues.values()), key=lambda r: r["due"])
    stop = plan["seconds"]

    def send_next(client: int) -> None:
        now = time.monotonic() - t0
        if now >= stop or not queues[client]:
            return
        req = queues[client].pop(0)
        req["due"] = now
        gen.start(req, gen.body(req), t0)

    gen.on_done = lambda conn: send_next(conn.req["client"])
    while first or gen.live:
        now = time.monotonic() - t0
        while first and first[0]["due"] <= now:
            send_next(first.pop(0)["client"])
        if now >= stop + plan["drain_s"]:
            gen.on_done = None
            for conn in list(gen.live.values()):
                conn.rec["cut"] = True
            gen.abandon("cut after the window's end")
            break
        wait = min(0.05, first[0]["due"] - now) if first else 0.05
        gen.pump(wait, t0)


def main(argv: list[str]) -> int:
    with open(argv[1]) as fh:
        plan = json.load(fh)
    gen = LoadGen(plan["port"], plan["vocab"], plan["seed"])
    warm_up(gen, plan["warmup"])
    print("warm", flush=True)
    word, t0 = sys.stdin.readline().split()
    if word != "go":
        return 2
    t0 = float(t0)
    (run_open if plan["loop"] == "open" else run_closed)(gen, plan, t0)
    with open(plan["out"], "w") as fh:
        json.dump({"t0": t0, "records": gen.records,
                   "stall_max_s": gen.stall_max}, fh)
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv))
