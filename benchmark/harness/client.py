"""The load generator: streaming clients over ``handle.stream`` (the loop
of bench.py --serve-llm, kept here so later PRs cannot change it), closed
loop and open loop.  Every request is timed on this process's clock; in
the open loop from when it was DUE, and how late the generator ran is
reported."""

from __future__ import annotations

import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Callable, Dict, List, Optional


def stream_one(handle, request: Dict[str, Any], due: Optional[float] = None,
               stop: Optional[threading.Event] = None) -> Dict[str, Any]:
    """Send one request and read its frames.  Times are perf_counter
    seconds.  ``ok`` only if every token frame and the done frame came.
    Once ``stop`` is set the stream is closed at its next frame (the
    client goes away, the engine frees the sequence): the record is
    ``cut``, neither completed nor failed."""
    payload = request["payload"]
    sent = time.perf_counter()
    rec: Dict[str, Any] = {"index": request["index"], "due": due,
                           "prompt": payload["prompt"],
                           "sent": sent, "frames": [], "tokens": [],
                           "ok": False, "error": None, "cut": False,
                           "greedy": request["greedy"],
                           "prompt_len": len(payload["prompt"]),
                           "max_tokens": payload["max_tokens"]}
    done = None
    try:
        stream = handle.stream(payload)
        for fr in stream:
            now = time.perf_counter()
            if stop is not None and stop.is_set():
                rec["cut"] = True
                stream.close()
                break
            if "error" in fr:
                rec["error"] = str(fr["error"])[:300]
                break
            if "token" in fr:
                rec["tokens"].append(int(fr["token"]))
                rec["frames"].append(now)
            if "done" in fr:
                done = fr
    except Exception as e:  # noqa: BLE001 — shed, timed out, interrupted
        rec["error"] = repr(e)[:300]
    rec["end"] = time.perf_counter()
    if rec["error"] is None and not rec["cut"]:
        if done is None or len(rec["tokens"]) != payload["max_tokens"]:
            rec["error"] = (f"truncated: {len(rec['tokens'])} of "
                            f"{payload['max_tokens']} tokens, done {done}")
        else:
            rec["ok"] = True
    return rec


def closed_loop(handle, pool: List[Dict[str, Any]], clients: int,
                seconds: float, vocab: int, seed: int,
                during: Optional[Callable] = None,
                full: Optional[Callable[[], bool]] = None,
                fill_limit_s: float = 60.0) -> Dict[str, Any]:
    """``clients`` threads, each sending its next request when the last
    one's done frame has arrived.  They take the pool's requests one after
    another from its head; a run that outlasts the pool starts it over with
    fresh token ids.  The window of ``seconds`` opens when ``full()`` first
    says that the engine's batch is full (the fill, a burst of prefills
    with no decode step between them, is set-up); at its end the clients
    go away: each closes its stream at its next frame.  Returns ``None``
    for ``t0`` where the batch did not fill within ``fill_limit_s``."""
    import numpy as np

    from .traffic import fill

    lock = threading.Lock()
    stop = threading.Event()
    records: List[Dict[str, Any]] = []
    taken = [0]

    def client(i: int) -> None:
        rng = np.random.default_rng([seed, 0x636c, i])
        while not stop.is_set():
            with lock:
                k = taken[0]
                taken[0] += 1
            req = pool[k % len(pool)]
            if k >= len(pool):
                req = fill(req["size"], req["index"], vocab, [], rng)
            rec = stream_one(handle, req, stop=stop)
            with lock:
                records.append(rec)

    threads = [threading.Thread(target=client, args=(i,),
                                name=f"client-{i}", daemon=True)
               for i in range(clients)]
    started = time.perf_counter()
    for th in threads:
        th.start()
    t0 = None
    while full is not None and not full():
        if time.perf_counter() - started > fill_limit_s:
            break
        time.sleep(0.1)
    else:
        t0 = time.perf_counter()
        if during is not None:
            during(t0)
        time.sleep(seconds)
    stop.set()
    for th in threads:
        th.join()
    return {"t0": t0, "t_end": None if t0 is None else t0 + seconds,
            "fill_s": None if t0 is None else t0 - started,
            "records": records, "drained": time.perf_counter()}


def open_loop(handle, requests: List[Dict[str, Any]], seconds: float,
              threads: int, during: Optional[Callable] = None
              ) -> Dict[str, Any]:
    """Send each request at its due time whatever the system does; one
    dispatcher sleeps to the due times and hands requests to a pool of
    reader threads."""
    records: List[Dict[str, Any]] = []
    lock = threading.Lock()
    pool = ThreadPoolExecutor(max_workers=threads,
                              thread_name_prefix="client")
    t0 = time.perf_counter()

    def one(req: Dict[str, Any], due: float) -> None:
        rec = stream_one(handle, req, due=due)
        with lock:
            records.append(rec)

    def dispatch() -> None:
        for req in requests:
            due = t0 + req["due_s"]
            wait = due - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            pool.submit(one, req, due)

    d = threading.Thread(target=dispatch, name="dispatcher", daemon=True)
    d.start()
    if during is not None:
        during(t0)
    d.join()
    pool.shutdown(wait=True)
    return {"t0": t0, "t_end": t0 + seconds, "records": records,
            "drained": time.perf_counter()}


def in_flight_at(records: List[Dict[str, Any]], t: float) -> int:
    """Requests sent (or due) by ``t`` and not finished by ``t``."""
    return sum(1 for r in records
               if (r["due"] if r["due"] is not None else r["sent"]) <= t
               < r["end"])
