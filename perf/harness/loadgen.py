"""The load generator: a child process that never imports jax.

    python perf/harness/loadgen.py <spec.json>

``spec`` (written by the parent): ``address``, ``endpoint``,
``clients``, ``ramp_seconds``, ``stagger_tokens``, ``seconds``,
``seed``, ``vocab``, ``deal`` (the requests as
``[[prompt length, token budget], ...]``, dealt to the clients in that order, again and again, whatever the
seed: the seed draws the token ids, so every seed offers the same
requests in the same order and a window holds the same work),
``loop`` ("closed").  The child connects every client, prints
``READY``, waits for a line on stdin, then runs the clients for
``ramp_seconds`` (the closed loop settles), then the window, and
prints one JSON object: the window's edges on its own clock and one
record per request with the client-side timestamps.  With
``stagger_tokens`` k, client ``i`` sends its first request when client
``i - 1`` has read the k-th token of its first answer: clients that all
start in one tick end in step and meet in convoys for the whole window,
and a start spread by the host's clock lands on another tick in every
run, while one spread by the engine's own tokens is the same in every
run and for every seed (PR 36).

The loops are copied from ``benchmark/serving_bench.py`` (keep-alive
HTTP/1.1, connect before the gate) and extended to read the chunked
ndjson stream of ``/generate`` token by token.
"""

import http.client
import itertools
import json
import random
import sys
import threading
import time


def cards(deck):
    """The deck ``[[value, copies], ...]`` as a sorted list."""
    return sorted(v for v, n in deck for _ in range(int(n)))


def check_deal(traffic):
    """``deal`` holds exactly the lengths of ``prompt_lengths`` and the
    budgets of ``max_tokens``: the weights the traffic states, and the
    shapes set-up warms, are those of the requests that are sent."""
    deal = traffic["deal"]
    if (sorted(t for t, _ in deal) != cards(traffic["prompt_lengths"])
            or sorted(b for _, b in deal) != cards(traffic["max_tokens"])):
        raise ValueError("traffic: 'deal' does not hold the cards of "
                         "'prompt_lengths' and 'max_tokens'")


def spec_of(traffic, address, seconds, seed, vocab):
    """The child's ``spec``: the traffic file's parameters and the
    run's (every generate driver writes this one)."""
    return {"address": address, "loop": traffic["loop"],
            "clients": traffic["clients"], "seconds": seconds,
            "ramp_seconds": float(traffic["ramp_seconds"]),
            "stagger_tokens": int(traffic.get("stagger_tokens", 0)),
            "seed": seed, "vocab": vocab, "deal": traffic["deal"]}


class Plan:
    """ONE stream of requests shared by all clients: ``deal`` in its
    own order, again and again.  A closed loop whose longest request
    lasts about as long as the window completes a few tens of requests
    in it, and which of them fall inside decides the rate (each
    admission stops all slots for one prefill), so the order is part of
    the traffic and not the seed's; the seed draws the token ids."""

    def __init__(self, spec):
        self._rng = random.Random(spec["seed"])
        self._deal = itertools.cycle(tuple(c) for c in spec["deal"])
        self._vocab, self._lock = spec["vocab"], threading.Lock()

    def next(self):
        with self._lock:
            T, budget = next(self._deal)
            # ids 2.. : 0 and 1 are the engine's eos and bos conventions
            return ([self._rng.randrange(2, self._vocab) for _ in range(T)],
                    budget)


def stream_generate(conn, prompt, max_tokens, on_token=None):
    """POST /generate and read the stream.  Returns (status, send time,
    [arrival time of each token], final line or None); ``on_token`` is
    called with the count after each token."""
    body = json.dumps({"src": prompt, "max_new_tokens": max_tokens})
    t_send = time.perf_counter()
    conn.request("POST", "/generate", body=body,
                 headers={"Content-Type": "application/json"})
    resp = conn.getresponse()
    if resp.status != 200:
        resp.read()
        return resp.status, t_send, [], None
    stamps, final = [], None
    while True:
        line = resp.readline()
        if not line:
            break
        now = time.perf_counter()
        msg = json.loads(line)
        if msg.get("done"):
            final = msg
            resp.read()  # the terminating chunk
            break
        stamps.append(now)
        if on_token is not None:
            on_token(len(stamps))
    return 200, t_send, stamps, final


def closed_loop(spec, go):
    """``clients`` callers, each sending its next request when the
    previous one has streamed to its end; client ``i`` starts at the
    ``stagger_tokens``-th token of client ``i - 1``'s first answer (or
    when that answer ends or fails, or the ramp is over, whichever
    comes first).  The window opens ``ramp_seconds`` after the first
    send.  No request is sent after the window closes; one still
    streaming then runs to its end, and the parent counts only the
    tokens stamped inside the window and only the requests sent inside
    it."""
    host, port = spec["address"].rsplit(":", 1)
    clients = int(spec["clients"])
    ramp = float(spec.get("ramp_seconds", 0))
    stagger = int(spec.get("stagger_tokens", 0))
    started = [threading.Event() for _ in range(clients)]
    records, lock = [], threading.Lock()
    todo = Plan(spec)
    gate = threading.Barrier(clients + 1)
    edges = {}

    def worker(idx):
        conn = http.client.HTTPConnection(host, int(port), timeout=120)
        conn.connect()
        mine = []
        gate.wait()          # all connected
        gate.wait()          # the go: the ramp starts
        if stagger and idx:
            started[idx - 1].wait(timeout=ramp)

        def on_token(n):
            if n == stagger:
                started[idx].set()

        while time.perf_counter() < edges["close"]:
            prompt, budget = todo.next()
            rec = {"client": idx, "prompt_len": len(prompt),
                   "max_tokens": budget}
            try:
                code, t_send, stamps, final = stream_generate(
                    conn, prompt, budget, on_token)
            except (OSError, http.client.HTTPException, ValueError) as e:
                rec.update(status=0, error=f"{type(e).__name__}: {e}",
                           t_send=None, stamps=[], complete=False)
                mine.append(rec)
                started[idx].set()
                conn.close()
                conn = http.client.HTTPConnection(host, int(port),
                                                  timeout=120)
                continue
            ok = (code == 200 and final is not None
                  and "error" not in final
                  and len(stamps) == len(final.get("ids", ())))
            rec.update(status=code, t_send=t_send, stamps=stamps,
                       complete=ok,
                       finish_reason=(final or {}).get("finish_reason"),
                       error=(final or {}).get("error"))
            mine.append(rec)
            started[idx].set()
        conn.close()
        with lock:
            records.extend(mine)

    threads = [threading.Thread(target=worker, args=(i,), daemon=True)
               for i in range(clients)]
    for t in threads:
        t.start()
    gate.wait()
    go()
    edges["go"] = time.perf_counter()
    edges["open"] = edges["go"] + ramp
    edges["close"] = edges["open"] + float(spec["seconds"])
    gate.wait()
    for t in threads:
        t.join()
    return edges, records


def main(argv):
    with open(argv[1]) as f:
        spec = json.load(f)
    if spec.get("loop", "closed") != "closed":
        raise SystemExit(f"loadgen: unknown loop {spec['loop']!r}")

    def go():
        print("READY", flush=True)
        sys.stdin.readline()

    edges, records = closed_loop(spec, go)
    print(json.dumps({"open": edges["open"], "close": edges["close"],
                      "end": time.perf_counter(), "records": records}),
          flush=True)


if __name__ == "__main__":
    main(sys.argv)
