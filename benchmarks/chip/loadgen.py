"""Closed-loop HTTP load generator: a child process that never imports JAX.

``clients`` threads (from the traffic mix) each send one ``POST /query`` at
a time and the next only when its answer has arrived, taking statements in
index order from the seed's stream (``traffic.Traffic.statement``).  No
statement starts after ``--seconds``; answers still in flight then are
awaited for up to ``--drain`` seconds more.

Standard output, one JSON object per line: ``{"start": t0}`` first, then one
record per statement sent (``i``, ``tpl``, ``t0``/``t1`` on the monotonic
clock, which every process of the host shares, ``status``, and the parsed
answer ``resp`` or an error ``err``), then ``{"end": t, "sent": n}``.

    python3 loadgen.py --port 8321 --mix dense-filters --domains \
        '{"l_discount": 11, "l_quantity": 51, "l_shipmode": 7, "l_shipinstruct": 4}' \
        --seed 7 --seconds 10
"""
from __future__ import annotations

import argparse
import http.client
import json
import sys
import threading
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

from traffic import Traffic, load_mix  # noqa: E402


def post(port: int, body: bytes, timeout: float):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request("POST", "/query", body=body,
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        return resp.status, resp.read()
    finally:
        conn.close()


def run(port: int, traffic: Traffic, seed: int, seconds: float,
        drain: float, out=sys.stdout) -> None:
    lock = threading.Lock()
    records = []
    next_i = [0]
    t0 = time.monotonic()
    t_end = t0 + seconds
    print(json.dumps({"start": t0}), file=out, flush=True)

    def client():
        while True:
            with lock:
                if time.monotonic() >= t_end:
                    return
                i = next_i[0]
                next_i[0] += 1
                rec = {"i": i, "tpl": None, "t0": None, "t1": None,
                       "status": None, "resp": None, "err": None}
                records.append(rec)
            tpl, body = traffic.statement(seed, i)
            data = json.dumps(body).encode()
            rec["tpl"] = tpl
            rec["t0"] = time.monotonic()
            try:
                status, raw = post(port, data, timeout=seconds + drain)
                rec["t1"] = time.monotonic()
                rec["status"] = status
                rec["resp"] = json.loads(raw)
            except Exception as exc:  # noqa: BLE001 - every failure counts
                rec["err"] = f"{type(exc).__name__}: {exc}"

    threads = [threading.Thread(target=client, daemon=True)
               for _ in range(traffic.clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=max(t_end + drain - time.monotonic(), 0.0))
    with lock:
        done = list(records)
    for rec in done:
        print(json.dumps(rec), file=out)
    print(json.dumps({"end": time.monotonic(), "sent": len(done)}),
          file=out, flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--port", type=int, required=True)
    ap.add_argument("--mix", required=True,
                    help="a mix name, or the path of its .json file")
    ap.add_argument("--domains", required=True,
                    help="JSON object: column -> number of ranks")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--drain", type=float, default=60.0)
    args = ap.parse_args(argv)
    traffic = Traffic(load_mix(args.mix), json.loads(args.domains))
    run(args.port, traffic, args.seed, args.seconds, args.drain)
    return 0


if __name__ == "__main__":
    sys.exit(main())
