"""The load generator: a child process that never imports JAX.

    python3 client.py <schedule.json> <records.jsonl> <url> <model> <drain_s>

Open loop: a session is started at its due time whether or not earlier ones have
finished; a later turn is due ``think_s`` after the previous answer ended. Every
time is this process's ``time.monotonic()`` (CLOCK_MONOTONIC, which the parent
shares). First it sends the primer, one request after another; then it prints
one line ``{"t0": <monotonic>}``: the schedule's zero. Sessions or turns due
after ``ramp_s + seconds`` are not sent. One record per turn goes to the
records file; a turn still unfinished ``drain_s`` after the window closed is
recorded as it stands (status 0 = never answered).
"""

from __future__ import annotations

import http.client
import json
import os
import sys
import threading
import time
from urllib.parse import urlsplit

CHAT_PATH = "/v1/chat/completions"


def _words(ids) -> str:
    return " ".join(str(i) for i in ids)


def _count_ids(text: str):
    """(ids in a content delta, whether every word was an id)."""
    words = text.split()
    ids = [int(w) for w in words if w.isdigit()]
    return ids, len(ids) == len(words)


def perform(host: str, port: int, model: str, messages: list, max_tokens: int,
            record: dict, deadline: float) -> None:
    """One streamed chat completion; fills ``record`` in place."""
    body = json.dumps({"model": model, "messages": messages, "max_tokens": max_tokens,
                       "temperature": 0, "stream": True}).encode()
    trace_id, span_id = os.urandom(16).hex(), os.urandom(8).hex()
    record["trace_id"] = trace_id
    conn = http.client.HTTPConnection(host, port, timeout=max(1.0, deadline - time.monotonic()))
    try:
        record["sent"] = time.monotonic()
        conn.request("POST", CHAT_PATH, body=body, headers={
            "Content-Type": "application/json",
            "traceparent": f"00-{trace_id}-{span_id}-01"})
        response = conn.getresponse()
        record["status"] = response.status
        if response.status != 200:
            response.read()
            return
        while True:
            line = response.fp.readline()
            if not line:
                break
            if not line.startswith(b"data: "):
                continue
            now = time.monotonic()
            payload = line[6:].strip()
            if payload == b"[DONE]":
                record["done"] = True
                break
            delta = json.loads(payload)["choices"][0]["delta"].get("content")
            if not delta:
                continue
            ids, clean = _count_ids(delta)
            if not clean:
                record["garbled"] = True
            if ids:
                record["events"].append([now, len(ids)])
                record["ids"].extend(ids)
    except (OSError, http.client.HTTPException, ValueError) as e:
        record["error"] = f"{type(e).__name__}: {e}"
    finally:
        record["end"] = time.monotonic()
        conn.close()


def run_session(session: dict, index: int, phase: str, t0: float, stop_at: float,
                deadline: float, host: str, port: int, model: str, out: list,
                lock: threading.Lock) -> None:
    history: list[dict] = []
    due = t0 + session["start_s"]
    for k, turn in enumerate(session["turns"]):
        if k:
            due = time.monotonic() + turn["think_s"]
        if due >= stop_at:
            return
        delay = due - time.monotonic()
        if delay > 0:
            time.sleep(delay)
        for message in turn["new_messages"]:
            history.append({"role": message["role"], "content": _words(message["ids"])})
        record = {"phase": phase, "session": index, "turn": k, "due": due, "sent": None,
                  "end": None, "status": 0, "done": False, "events": [], "ids": [],
                  "max_tokens": turn["max_tokens"],
                  "numeric_prompt": [i for m in history for i in map(int, m["content"].split())]}
        with lock:
            out.append(record)
        perform(host, port, model, list(history), turn["max_tokens"], record, deadline)
        if not record["done"]:
            return
        history.append({"role": "assistant", "content": _words(record["ids"])})


def main(argv: list[str]) -> int:
    schedule_path, records_path, url, model, drain_s = argv
    schedule = json.loads(open(schedule_path).read())
    parts = urlsplit(url)
    host, port = parts.hostname, parts.port
    records: list[dict] = []
    lock = threading.Lock()
    far = time.monotonic() + 600.0
    for i, session in enumerate(schedule["primer"]):
        run_session(session, i, "primer", time.monotonic(), far, far, host, port, model, records, lock)
    t0 = time.monotonic() + 0.05
    print(json.dumps({"t0": t0}), flush=True)
    stop_at = t0 + schedule["ramp_s"] + schedule["seconds"]
    deadline = stop_at + float(drain_s)
    threads = []
    for i, session in enumerate(sorted(schedule["sessions"], key=lambda s: s["start_s"])):
        delay = t0 + session["start_s"] - time.monotonic()
        if delay > 0:
            time.sleep(delay)
        thread = threading.Thread(
            target=run_session, daemon=True,
            args=(session, i, "run", t0, stop_at, deadline, host, port, model, records, lock))
        thread.start()
        threads.append(thread)
    for thread in threads:
        thread.join(timeout=max(0.0, deadline - time.monotonic()))
    with lock:
        snapshot = json.loads(json.dumps(records))
    with open(records_path, "w") as f:
        for record in snapshot:
            f.write(json.dumps(record) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
