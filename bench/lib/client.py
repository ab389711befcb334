"""Stdlib-only load client: drives ``POST /v1/completions`` with
``stream: true`` and records client-side send, first-token and per-token
receive times. Runs as a child process of the harness and never imports
JAX, so it neither touches the chip nor shares the server's interpreter.

Protocol: one JSON object on stdin (port, mix, seed, vocab, seconds), then
``READY`` on stdout once every prompt is built; the parent answers ``GO``
on stdin to start the clock. The client sends for ``warm_in_s`` plus
``seconds``, stops sending at the window's close, waits as the mix says
(see the end of ``main``), cuts every stream still open (the server
cancels them), and prints one JSON object with every
request's record as its last stdout line. Times are ``time.perf_counter``
readings, which on Linux share one monotonic clock with the server's.
"""

from __future__ import annotations

import json
import os
import socket
import sys
import threading
import time
from typing import Any, Dict, List, Optional

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import traffic  # noqa: E402  (stdlib-only sibling)


class Stream:
    """One streamed completion over a raw socket."""

    def __init__(self, port: int, rec: Dict[str, Any], body: bytes):
        self.port, self.rec, self.body = port, rec, body
        self.sock: Optional[socket.socket] = None
        self.cut = False

    def run(self) -> None:
        rec = self.rec
        try:
            self.sock = socket.create_connection(("127.0.0.1", self.port),
                                                 timeout=600.0)
            rec["sent"] = time.perf_counter()
            self.sock.sendall(
                b"POST /v1/completions HTTP/1.1\r\nHost: bench\r\n"
                b"Content-Type: application/json\r\nContent-Length: "
                + str(len(self.body)).encode() + b"\r\n\r\n" + self.body)
            self._read()
        except OSError as e:
            if not self.cut:
                rec["error"] = f"{type(e).__name__}: {e}"
        finally:
            if self.cut and rec.get("status") is None:
                rec["status"] = "CUT"
            if self.sock is not None:
                try:
                    self.sock.close()
                except OSError:
                    pass

    def _read(self) -> None:
        rec, buf, head = self.rec, b"", None
        while True:
            chunk = self.sock.recv(65536)
            now = time.perf_counter()
            if not chunk:
                return
            buf += chunk
            if head is None:
                if b"\r\n\r\n" not in buf:
                    continue
                head, _, buf = buf.partition(b"\r\n\r\n")
                rec["http"] = int(head.split(b" ", 2)[1])
                if rec["http"] != 200:
                    rec["status"] = "HTTP_ERROR"
                    return
            while b"\n\n" in buf:
                event, _, buf = buf.partition(b"\n\n")
                if not event.startswith(b"data: "):
                    continue
                data = event[6:]
                if data == b"[DONE]":
                    return
                obj = json.loads(data)
                if "token" in obj:
                    if rec["first"] is None:
                        rec["first"] = now
                        rec["rid"] = obj.get("rid")
                    rec["times"].append(now)
                    rec["tokens"].append(obj["token"])
                elif "status" in obj:
                    rec["status"] = obj["status"]
                    rec["rid"] = obj.get("rid", rec.get("rid"))
                    if obj.get("error"):
                        rec["error"] = obj["error"]

    def close(self) -> None:
        self.cut = True
        if self.sock is not None:
            try:
                self.sock.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass


def new_record(req: Dict[str, Any], sched: float) -> Dict[str, Any]:
    return {"i": req["i"], "prompt_len": req["prompt_len"],
            "max_tokens": req["max_tokens"], "sched": sched, "sent": None,
            "first": None, "times": [], "tokens": [], "rid": None,
            "status": None, "http": None}


def main() -> None:
    spec = json.loads(sys.stdin.readline())
    mix = traffic.load_mix(spec["mix"])
    reqs = traffic.plan(mix, spec["seed"], spec["vocab"],
                        traffic.window_requests(mix, spec["seconds"]))
    bodies = [json.dumps({"prompt": traffic.prompt_tokens(r, spec["vocab"]),
                          "max_tokens": r["max_tokens"], "temperature": 0.0,
                          "stream": True}).encode() for r in reqs]
    port = spec["port"]
    warm_in = float(mix.get("warm_in_s", 0.0))
    wait_first = float(spec.get("first_token_wait_s", 60.0))
    print("READY", flush=True)
    if sys.stdin.readline().strip() != "GO":
        return
    start = time.perf_counter()
    t0 = start + warm_in
    t1 = t0 + float(spec["seconds"])
    records: List[Dict[str, Any]] = []
    streams: List[Stream] = []
    threads: List[threading.Thread] = []
    lock = threading.Lock()

    def launch(i: int, sched: float) -> Stream:
        rec = new_record(reqs[i], sched)
        st = Stream(port, rec, bodies[i])
        with lock:
            records.append(rec)
            streams.append(st)
        return st

    if mix["loop"] == "open":
        for i, r in enumerate(reqs):
            sched = start + r["offset_s"]
            if sched >= t1:
                break
            delay = sched - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
            th = threading.Thread(target=launch(i, sched).run, daemon=True)
            th.start()
            threads.append(th)
    else:
        nxt = [0]

        def client() -> None:
            while True:
                now = time.perf_counter()
                if now >= t1:
                    return
                with lock:
                    i = nxt[0] % len(reqs)    # the pool repeats if spent
                    nxt[0] += 1
                launch(i, now).run()

        for _ in range(int(mix["clients"])):
            th = threading.Thread(target=client, daemon=True)
            th.start()
            threads.append(th)
    time.sleep(max(0.0, t1 - time.perf_counter()))
    # the window has closed: an open loop's requests scheduled inside it
    # may still wait for their first token (TTFT counts the wait); a
    # closed loop sends nothing more but keeps its streams open for the
    # mix's ``finish_wait_s``, so that more answers finish, at the same
    # load, for the correctness sample. Then cut what is open
    if mix["loop"] == "open":
        deadline = time.perf_counter() + wait_first

        def waiting(s):
            return (s.rec["first"] is None and s.rec["status"] is None
                    and s.rec["sched"] >= t0)
    else:
        deadline = time.perf_counter() + float(mix.get("finish_wait_s", 0))

        def waiting(s):
            return s.rec["status"] is None
    while time.perf_counter() < deadline:
        with lock:
            pending = [s for s in streams if waiting(s)]
        if not pending:
            break
        time.sleep(0.01)
    with lock:
        for st in streams:
            st.close()
    for th in threads:
        th.join(timeout=30.0)
    late = [r["sent"] - r["sched"] for r in records if r["sent"] is not None]
    print(json.dumps({"start": start, "t0": t0, "t1": t1,
                      "alive_threads": sum(th.is_alive() for th in threads),
                      "late_max_s": max(late, default=0.0),
                      "late_mean_s": sum(late) / len(late) if late else 0.0,
                      "requests": records}), flush=True)


if __name__ == "__main__":
    main()
