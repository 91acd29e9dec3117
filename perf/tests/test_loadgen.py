"""The closed-loop generator's accounting of attempted and failed, and
the requests every seed deals in the same order."""

import http.server
import json
import threading

import pytest

from perf.drivers.generate import client_metrics
from perf.harness import loadgen


class _Handler(http.server.BaseHTTPRequestHandler):
    protocol_version = "HTTP/1.1"

    def log_message(self, *a):
        pass

    def do_POST(self):
        n = int(self.headers["Content-Length"])
        body = json.loads(self.rfile.read(n))
        self.server.seen.append(body)
        if len(body["src"]) == 3:          # the request that is refused
            msg = json.dumps({"error": "no", "reason": "too_long"}).encode()
            self.send_response(503)
            self.send_header("Content-Length", str(len(msg)))
            self.end_headers()
            self.wfile.write(msg)
            return
        self.send_response(200)
        self.send_header("Transfer-Encoding", "chunked")
        self.end_headers()
        ids = list(range(body["max_new_tokens"]))
        for line in [{"token": t} for t in ids] + [
                {"done": True, "ids": ids, "finish_reason": "length"}]:
            data = (json.dumps(line) + "\n").encode()
            self.wfile.write(f"{len(data):x}\r\n".encode() + data + b"\r\n")
        self.wfile.write(b"0\r\n\r\n")


@pytest.fixture
def server():
    srv = http.server.ThreadingHTTPServer(("127.0.0.1", 0), _Handler)
    srv.seen = []
    th = threading.Thread(target=srv.serve_forever, daemon=True)
    th.start()
    yield srv
    srv.shutdown()
    th.join(timeout=10)


def test_closed_loop_counts_attempted_and_failed(server):
    spec = {"address": "127.0.0.1:%d" % server.server_address[1],
            "clients": 2, "seconds": 0.5, "seed": 5, "vocab": 50,
            "deal": [[8, 4], [3, 2], [8, 2], [8, 4]]}
    edges, records = loadgen.closed_loop(spec, go=lambda: None)
    out = {"open": edges["open"], "close": edges["close"],
           "end": edges["close"], "records": records}
    cm = client_metrics(out)
    assert cm["attempted"] == len(records) == len(server.seen) > 4
    refused = [r for r in records if r["prompt_len"] == 3]
    assert refused and all(r["status"] == 503 and not r["complete"]
                           for r in refused)
    assert cm["failed"] == len(refused)
    ok = [r for r in records if r["complete"]]
    assert all(len(r["stamps"]) == r["max_tokens"] for r in ok)
    assert cm["tokens"] <= sum(len(r["stamps"]) for r in ok)
    assert len(cm["ttft_ms"]) == len(ok)
    # token i >= 1 of a request reads prompt_len + i rows
    one = [r for r in ok if r["max_tokens"] == 2][0]
    assert one["prompt_len"] + 1 <= cm["kv_rows"]


def test_staggered_first_sends(server):
    """Client i first sends when client i - 1 has read the k-th token
    of its first answer, whatever the seed."""
    spec = {"address": "127.0.0.1:%d" % server.server_address[1],
            "clients": 4, "seconds": 0.2, "ramp_seconds": 0.3,
            "stagger_tokens": 3, "seed": 7, "vocab": 50,
            "deal": [[8, 5], [9, 5], [10, 5], [11, 5]]}
    edges, records = loadgen.closed_loop(spec, go=lambda: None)
    first = [min((r for r in records if r["client"] == c),
                 key=lambda r: r["t_send"]) for c in range(4)]
    for a, b in zip(first, first[1:]):
        assert b["t_send"] >= a["stamps"][2]      # after its third token
    assert edges["open"] == pytest.approx(edges["go"] + 0.3)


def test_spec_carries_the_traffic_parameters():
    traffic = dict(TRAFFIC, loop="closed", clients=4, ramp_seconds=10,
                   stagger_tokens=3)
    spec = loadgen.spec_of(traffic, "h:1", 30.0, 2 ** 31 + 11, 100)
    assert (spec["stagger_tokens"], spec["ramp_seconds"]) == (3, 10.0)
    assert spec["deal"] == TRAFFIC["deal"] and spec["seed"] == 2 ** 31 + 11
    del traffic["stagger_tokens"]
    assert loadgen.spec_of(traffic, "h:1", 1, 0, 9)["stagger_tokens"] == 0


TRAFFIC = {"prompt_lengths": [[64, 2], [128, 1]],
           "max_tokens": [[32, 1], [64, 2]],
           "deal": [[64, 64], [128, 32], [64, 64]]}


def test_every_seed_deals_the_same_requests_in_the_same_order():
    loadgen.check_deal(TRAFFIC)
    with pytest.raises(ValueError):
        loadgen.check_deal(dict(TRAFFIC, deal=[[64, 64], [128, 32]]))
    hands = []
    for seed in (1, 2 ** 31 + 11):
        plan = loadgen.Plan(dict(TRAFFIC, vocab=100, seed=seed))
        hand = [plan.next() for _ in range(7)]
        assert [(len(p), b) for p, b in hand] == [
            tuple(TRAFFIC["deal"][i % 3]) for i in range(7)]
        assert all(2 <= t < 100 for p, _ in hand for t in p)
        hands.append(hand)
    assert hands[0] != hands[1]        # other token ids, the same sizes
