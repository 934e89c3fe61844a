"""The load generator: a child process on the other side of the socket.

    python3 -m perfbench.loadgen <schedule.json> <port> <out.json>

Stdlib only — it never imports jax or paddle_tpu, so it never touches the
chip and shares no interpreter lock with the engine it loads. It replays the
schedule file (requests with ``due`` offsets and token ids) as an open loop
of streaming ``POST /v1/generate`` calls: one thread per open stream,
``time.monotonic()`` stamps for due, sent and every token's arrival.

Its first line of output is ``T0 <seconds>``: the ``time.monotonic()`` of
offset zero. On Linux that clock is one for all processes, so the parent
cuts its own window at the same instants.

It stops sending once every counted request has ended (or the schedule is
exhausted), waits for the counted ones up to the drain limit, writes the out
file and exits; streams still open then are lead-out traffic and die with
the process.
"""

from __future__ import annotations

import http.client
import json
import sys
import threading
import time

_START_DELAY_S = 0.5          # offset zero lies this far after start-up


def _stream(port: int, body: bytes, rec: dict) -> None:
    """One request: send, stamp each token as its frame arrives."""
    stamps = rec["tokens"]
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=300)
    try:
        rec["sent"] = time.monotonic()
        conn.request("POST", "/v1/generate", body=body)
        resp = conn.getresponse()
        if resp.status != 200:
            rec["error"] = f"status {resp.status}: {resp.read(200)!r}"
            return
        event = b""
        while True:
            line = resp.readline()
            if not line:
                break
            if line.startswith(b"data: "):
                if event == b"":
                    stamps.append(time.monotonic())
                elif event == b"done":
                    rec["ok"] = True
                else:
                    rec["error"] = line[6:200].decode("utf-8", "replace")
            elif line.startswith(b"event: "):
                event = line[7:].strip()
            elif line in (b"\n", b"\r\n"):
                event = b""
    except Exception as exc:                 # recorded, judged by the parent
        rec["error"] = f"{type(exc).__name__}: {exc}"
    finally:
        rec["ended"] = time.monotonic()
        conn.close()


def main(argv) -> int:
    sched_path, port, out_path = argv[1], int(argv[2]), argv[3]
    with open(sched_path) as f:
        doc = json.load(f)
    requests = doc["requests"]
    bodies = [json.dumps({"prompt": r["prompt"],
                          "max_new_tokens": r["new_tokens"],
                          "stream": True}).encode() for r in requests]
    recs = [{"id": r["id"], "counted": r["counted"],
             "prompt_len": r["prompt_len"], "new_tokens": r["new_tokens"],
             "due": None, "sent": None, "tokens": [], "ok": False}
            for r in requests]
    t0 = time.monotonic() + _START_DELAY_S
    print(f"T0 {t0!r}", flush=True)

    pending = [rec for rec in recs if rec["counted"]]
    threads = []
    for r, body, rec in zip(requests, bodies, recs):
        if not r["counted"] and pending and \
                all("ended" in p for p in pending) and \
                r["due"] > doc["window_end"]:
            break                            # lead-out has done its work
        rec["due"] = t0 + r["due"]
        delay = rec["due"] - time.monotonic()
        if delay > 0:
            time.sleep(delay)
        th = threading.Thread(target=_stream, args=(port, body, rec),
                              daemon=True)
        th.start()
        threads.append((rec, th))

    deadline = t0 + doc["window_end"] + doc["drain_limit_s"]
    for rec, th in threads:
        if rec["counted"]:
            th.join(max(0.0, deadline - time.monotonic()))
    with open(out_path, "w") as f:
        # copies: lead-out streams are still appending stamps
        json.dump({"t0": t0, "requests": [
            dict(r, tokens=list(r["tokens"]))
            for r in recs if r["due"] is not None]}, f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
