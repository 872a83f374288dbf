"""An open loop of JSON-RPC reads against the node while it imports.

Arrivals are Poisson at a rate fixed in the traffic file (never searched
for in a run), drawn from the seed before the window opens together with
each request's method and keys. A small pool of sender threads with
keep-alive connections takes requests in due order: a sender sleeps until
the request is due, sends, and records due, sent and done times. A
request is timed **from when it was due**, so a stall of the server is
charged to every request that waited behind it, and how late the
generator itself ran (sent - due) is reported beside it. A request due
inside the window but still unsent when it closes, or answered with an
error, has failed and ranks above every answered one; a reply in flight
at the close is waited for.

Parameters (``rpc`` block of a traffic file): ``rate`` requests/s;
``mix`` method -> weight; ``zipf`` the YCSB constant for account keys;
``senders`` threads; ``logs_span`` blocks per eth_getLogs.

Answers are checked after the window against what cannot depend on the
moving head: a block's stored state root and hash, a receipt's block
number, and for balances and token slots (which only grow for the plain
accounts asked about) the plain ledger's value at the window's opening
head and at its closing head as bounds.
"""

from __future__ import annotations

import http.client
import json
import threading
import time
from typing import Dict, List

import numpy as np

from benchmark.generators import accounts as gen_accounts
from benchmark.lib.outcome import Check
from benchmark.reference import ledger as ref_ledger


def schedule(params: Dict, seconds: float, n_accounts: int, seed: int):
    """Due offsets (s), method index and account index per request."""
    rng = np.random.default_rng([seed, 0x727063])
    # a number, or for the one-off sweep [[seconds, rate], ...] stages
    stages = params["rate"]
    if not isinstance(stages, list):
        stages = [[seconds, stages]]
    parts, t0 = [], 0.0
    for length, rate in stages:
        length = min(float(length), seconds - t0)
        if length <= 0:
            break
        n = int(rate * length * 1.2) + 16
        d = np.cumsum(rng.exponential(1.0 / float(rate), n))
        parts.append(t0 + d[d < length])
        t0 += length
    due = np.concatenate(parts)
    methods = sorted(params["mix"])
    weights = np.array([params["mix"][m] for m in methods], dtype=float)
    which = rng.choice(len(methods), len(due), p=weights / weights.sum())
    ranks = np.arange(1, n_accounts + 1, dtype=float)
    p = ranks ** -float(params["zipf"])
    order = rng.permutation(n_accounts)  # which account holds which rank
    account = order[rng.choice(n_accounts, len(due), p=p / p.sum())]
    block_pick = rng.random(len(due))
    return due, methods, which, account, block_pick


class OpenLoop:
    def __init__(self, port: int, params: Dict, data: Dict, seed: int,
                 head: int, tx_hashes: List[bytes] = ()):
        self.port, self.params, self.data = port, params, data
        self.seed, self.head0 = seed, head
        self.tx_hashes = list(tx_hashes)
        self.threads: List[threading.Thread] = []
        self.rows: List = []          # (i, due, sent, done, answer)
        self._next = 0
        self._lock = threading.Lock()
        self._stop = False

    # ------------------------------------------------------- requests

    def _bodies(self, seconds: float):
        d, p = self.data, self.params
        hx = lambda b: "0x" + b.hex()
        due, methods, which, account, pick = schedule(
            p, seconds, len(d["others"]), self.seed)
        holders = [d["others"][int(a)] for a in account]
        slots = ref_ledger.token_slots(holders)
        bodies = []
        for i in range(len(due)):
            m = methods[int(which[i])]
            n = 1 + int(pick[i] * self.head0)      # a block already in
            if m == "eth_getBalance":
                params = [hx(holders[i]), "latest"]
            elif m == "eth_getStorageAt":
                params = [hx(d["token"]), hex(slots[i]), "latest"]
            elif m == "eth_getBlockByNumber":
                params = [hex(n), False]
            elif m == "eth_getTransactionReceipt":
                params = [hx(self.tx_hashes[int(pick[i] * len(self.tx_hashes))])]
            elif m == "eth_getLogs":
                lo = max(1, self.head0 - int(p["logs_span"]) + 1)
                params = [{"fromBlock": hex(lo), "toBlock": hex(self.head0)}]
            else:
                raise ValueError(f"no request shape for {m!r}")
            bodies.append((m, n, int(account[i]), json.dumps({
                "jsonrpc": "2.0", "id": i, "method": m,
                "params": params}).encode()))
        return due, bodies

    def warm(self) -> None:
        """One request of every method before the window: connections,
        lazy imports and per-method first-call work are set-up."""
        _due, bodies = self._bodies(2.0)
        seen = set()
        conn = http.client.HTTPConnection("127.0.0.1", self.port, timeout=60)
        for m, _n, _a, body in bodies:
            if m not in seen:
                seen.add(m)
                self._post(conn, body)
        conn.close()

    @staticmethod
    def _post(conn, body: bytes):
        conn.request("POST", "/", body=body,
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        answer = json.loads(resp.read())
        # the server's own dispatch time, where it states one
        served = resp.getheader("X-Khipu-Served-Ms")
        if served is not None:
            answer["served_ms"] = float(served)
        return answer

    # ---------------------------------------------------------- run

    def start(self, t_open: float, seconds: float) -> None:
        self.t_open = t_open
        self.due, self.bodies = self._bodies(seconds)
        for k in range(int(self.params["senders"])):
            t = threading.Thread(target=self._sender, name=f"rpcload-{k}",
                                 daemon=True)
            t.start()
            self.threads.append(t)

    def _sender(self) -> None:
        conn = http.client.HTTPConnection("127.0.0.1", self.port, timeout=60)
        try:
            while not self._stop:
                with self._lock:
                    i = self._next
                    if i >= len(self.due):
                        return
                    self._next += 1
                due = self.t_open + float(self.due[i])
                wait = due - time.perf_counter()
                while wait > 0 and not self._stop:
                    time.sleep(min(wait, 0.05))
                    wait = due - time.perf_counter()
                if self._stop:
                    self.rows.append((i, due, None, None, None))
                    return
                sent = time.perf_counter()
                try:
                    answer = self._post(conn, self.bodies[i][3])
                except Exception as e:
                    answer = {"error": f"{type(e).__name__}: {e}"}
                    conn.close()
                    conn = http.client.HTTPConnection(
                        "127.0.0.1", self.port, timeout=60)
                self.rows.append((i, due, sent, time.perf_counter(), answer))
        finally:
            conn.close()

    def finish(self, t_close: float, head1: int) -> Dict:
        """Stop the senders, wait for them, and reduce. ``head1`` is the
        node's head when the window closed."""
        self._stop = True
        for t in self.threads:
            t.join(timeout=90)
        alive = sum(t.is_alive() for t in self.threads)
        window_ms = 1000.0 * (t_close - self.t_open)
        rows = {r[0]: r for r in self.rows}
        n_due = int(np.searchsorted(self.due, t_close - self.t_open))
        latency, lateness, served, failed, wrong = [], [], [], 0, 0
        lo = self._ledger(self.head0)
        hi = self._ledger(head1)
        timeline = []   # (due, sent, done) offsets from the opening
        errors: Dict[str, int] = {}
        for i in range(n_due):
            r = rows.get(i)
            timeline.append((float(self.due[i]),) + tuple(
                None if r is None or x is None else x - self.t_open
                for x in (r[2:4] if r else (None, None))))
            # sent before the close and answered (a reply in flight at
            # the close is waited for); still queued at the close = failed
            ok = r is not None and r[3] is not None and "result" in r[4]
            if not ok:
                failed += 1
                why = ("unsent at the close" if r is None or r[3] is None
                       else f"{self.bodies[i][0]}: {str(r[4].get('error'))[:120]}")
                errors[why] = errors.get(why, 0) + 1
                continue
            latency.append(1000.0 * (r[3] - r[1]))
            lateness.append(1000.0 * (r[2] - r[1]))
            if "served_ms" in r[4]:
                served.append(r[4]["served_ms"])
            wrong += not self._answer_ok(i, r[4]["result"], lo, hi)
        ranked = sorted(latency) + [window_ms] * failed
        p95 = ranked[min(len(ranked) - 1, int(0.95 * len(ranked)))] \
            if ranked else window_ms
        return {
            "due": n_due, "failed": failed, "p95_ms": p95,
            "latency_ms": latency, "lateness_ms": lateness,
            "served_ms": served,
            "rows": timeline,
            "answered": len(latency), "errors": errors,
            "checks": [
                Check("rpc_answers_wrong_of_%d" % len(latency), wrong, 0),
                Check("rpc_sender_threads_left", alive, 0),
                Check("rpc_nothing_answered", int(not latency), 0),
            ],
        }

    # -------------------------------------------------------- answers

    def _ledger(self, head: int):
        d = self.data
        balances = ref_ledger.plain_balances(
            gen_accounts.PLAIN_BALANCE_BASE, d["extra"], d["picks"], head)
        held, _ = ref_ledger.token_balances(
            len(d["others"]), len(d["senders"]), d["picks"], head)
        return balances, held

    def _answer_ok(self, i: int, result, lo, hi) -> bool:
        m, n, account, _ = self.bodies[i]
        if m == "eth_getBalance":
            return lo[0][account] <= int(result, 16) <= hi[0][account]
        if m == "eth_getStorageAt":
            return lo[1][account] <= int(result, 16) <= hi[1][account]
        if m == "eth_getBlockByNumber":
            return (result is not None and int(result["number"], 16) == n
                    and result["stateRoot"] == "0x"
                    + self.data["roots"][n - 1].hex())
        if m == "eth_getTransactionReceipt":
            return result is not None and \
                1 <= int(result["blockNumber"], 16) <= self.head0
        if m == "eth_getLogs":
            return isinstance(result, list)
        return False
