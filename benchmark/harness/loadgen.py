"""The load generator of a serving cell: a process of its own.

The process that holds the chip starts this file as a child, with
``JAX_PLATFORMS=cpu`` in its environment, so that the clients' threads
do not share an interpreter lock with the server's scheduler. It reads a
schedule (``schedule.json``: the server's address, the model's route and
the requests with their due times), answers ``ready`` on standard output,
waits on standard input for ``go <t0>`` (the window's start on
``time.monotonic()``, which both processes of one Linux host share; the
lead-in's requests are due before it), sends every request at its due
time whether or not earlier ones have finished (an open loop), stamps
every token as it arrives, and writes ``out.json`` when every
stream has ended or the drain limit has passed.

It never touches a JAX backend: the client is stdlib HTTP.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time
from typing import Any, Dict, List

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def stream_one(client, model: str, request: Dict[str, Any], t0: float,
               deadline_ms: float, row: Dict[str, Any]) -> None:
    """Send one request and stamp its tokens into ``row``."""
    row["sent_s"] = time.monotonic() - t0
    try:
        stream = client.generate(
            model, request["prompt"],
            max_new_tokens=request["max_new_tokens"],
            deadline_ms=deadline_ms, correlation_id=request["cid"])
        for token in stream:
            row["token_s"].append(time.monotonic() - t0)
            row["tokens"].append(token)
        row["done"] = True
    except Exception as e:  # noqa: BLE001 - every failure is the request's
        row["error"] = f"{type(e).__name__}: {e}"[:300]


def drive(schedule: Dict[str, Any], t0: float) -> List[Dict[str, Any]]:
    """Send the schedule from ``t0`` on and wait for the streams."""
    from deeplearning4j_tpu.serving.client import ServingClient

    limit_s = schedule["seconds"] + schedule["drain_s"]
    client = ServingClient(schedule["url"], timeout=limit_s)
    rows = [{"index": r["index"], "due_s": r["due_s"], "sent_s": None,
             "token_s": [], "tokens": [], "done": False, "error": None}
            for r in schedule["requests"]]
    threads = []
    for request, row in zip(schedule["requests"], rows):
        wait = t0 + request["due_s"] - time.monotonic()
        if wait > 0:
            time.sleep(wait)
        thread = threading.Thread(
            target=stream_one, daemon=True,
            args=(client, schedule["model"], request, t0,
                  limit_s * 1e3, row))
        thread.start()
        threads.append(thread)
    for thread in threads:
        thread.join(max(0.0, t0 + limit_s - time.monotonic()))
    for row, thread in zip(rows, threads):
        if thread.is_alive() and row["error"] is None:
            row["error"] = "unfinished at the drain limit"
    return rows


def main(argv=None) -> int:
    schedule_path, out_path = (argv or sys.argv[1:])[:2]
    with open(schedule_path, encoding="utf-8") as f:
        schedule = json.load(f)
    from deeplearning4j_tpu.serving.client import ServingClient  # noqa: F401

    print("ready", flush=True)
    word, t0 = sys.stdin.readline().split()
    if word != "go":
        return 2
    rows = drive(schedule, float(t0))
    with open(out_path + ".tmp", "w", encoding="utf-8") as f:
        json.dump({"end_s": time.monotonic() - float(t0), "rows": rows}, f)
    os.replace(out_path + ".tmp", out_path)
    return 0


if __name__ == "__main__":
    # the streams' daemon threads may still hold sockets at the drain
    # limit: leave without waiting for them
    sys.stdout.flush()
    os._exit(main())
