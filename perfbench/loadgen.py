"""The benchmark's load generator: ONE general generator that reads a
traffic file (``perfbench/traffic/<name>.json``) and drives a
``ServingFrontend`` over the wire with ``ServingClient``s.

It runs as a child process that starts no accelerator backend
(``JAX_PLATFORMS=cpu``; one process holds the chip), so its threads do
not share the server's interpreter lock::

    parent -> child stdin   line 1: the spec (traffic, seed, seconds, ...)
    child  -> parent stdout "ready"
    parent -> child stdin   line 2: {"address": [...], "t_open": <time.time()>}
    child  -> parent stdout "done"; records in spec["out"]

* **open loop**: arrivals on a schedule made from the seed, each request
  timed from the instant it was DUE, whether or not earlier ones finished;
  how late the generator ran is reported.
* **closed loop**: ``clients`` callers, each sending its next request when
  its last ended.

Every seed gets the SAME multiset of gaps and of (source, target) length
pairs (the distribution's quantiles), in another order: the seed changes
the order of the work, not the work. A request ends when the client holds
its drawn target length: it closes the stream, which is the wire's in-band
cancel and frees the slot and its pages at once. That stands for the EOS a
trained model would emit and seeded weights never do.
"""

import gc
import json
import math
import os
import queue
import statistics
import sys
import threading
import time

import numpy as np

_NORMAL = statistics.NormalDist()


# -- the plan: pure arithmetic, tested on its own -----------------------------

def quantile_points(n):
    return (np.arange(n, dtype=np.float64) + 0.5) / n


def draw_lengths(spec, n, base=None):
    """``n`` lengths at the quantiles of ``spec``'s distribution, in
    quantile order (the caller permutes)."""
    u = quantile_points(n)
    kind = spec["dist"]
    if kind == "lognormal":
        z = np.array([_NORMAL.inv_cdf(x) for x in u])
        vals = np.exp(math.log(spec["median"]) + spec["sigma"] * z)
    elif kind == "ratio_uniform":
        # a factor at uniform quantiles, paired with ``base`` by the caller
        vals = base * (spec["low"] + (spec["high"] - spec["low"]) * u)
    else:
        raise ValueError("unknown length distribution %r" % kind)
    return np.clip(np.rint(vals), spec["min"], spec["max"]).astype(np.int64)


def exponential_gaps(n, rate):
    return -np.log1p(-quantile_points(n)) / float(rate)


def make_plan(traffic, seed, seconds):
    """{"src_len", "trg_len", "ramp", "seconds"} and, for an open loop,
    "due": offsets from the window's opening (negative: the ramp).

    The gaps are the exponential distribution's quantiles and the lengths
    the length distributions', so every seed offers the same work; the
    seed draws a free permutation of each, and nothing else is laid out.
    The arrivals are then a Poisson process conditioned on its count over
    ramp and window (bursts and lulls of any length, as independent users
    make them). A closed loop's callers go round a plan of four requests a
    caller, so a run of any length sends whole turns of the same work."""
    seconds, ramp = float(seconds), float(traffic["ramp_s"])
    if traffic["loop"] == "open":
        n = int(round(traffic["rate_rps"] * (ramp + seconds)))
    else:
        n = int(traffic["clients"]) * 4
    # which target factor goes with which source is the traffic's own and
    # the same for every seed; the seed orders the pairs
    src = draw_lengths(traffic["src_len"], n)[
        np.random.RandomState(0).permutation(n)]
    trg = draw_lengths(traffic["trg_len"], n, base=src)
    rng = np.random.RandomState(int(seed) % (2 ** 32))
    order = rng.permutation(n)
    plan = {"src_len": src[order], "trg_len": trg[order],
            "ramp": ramp, "seconds": seconds}
    if traffic["loop"] == "open":
        gaps = exponential_gaps(n, traffic["rate_rps"])[rng.permutation(n)]
        due = np.cumsum(gaps) - gaps[0] - ramp
        keep = due < seconds
        plan["src_len"], plan["trg_len"] = (plan["src_len"][keep],
                                            plan["trg_len"][keep])
        plan["due"] = due[keep]
    return plan


def percentile(values, q):
    """The ``q``-th percentile (0-100) by linear interpolation between
    order statistics; None for no values."""
    vals = sorted(values)
    if not vals:
        return None
    k = (len(vals) - 1) * q / 100.0
    lo, hi = int(math.floor(k)), int(math.ceil(k))
    return vals[lo] + (vals[hi] - vals[lo]) * (k - lo)


def summarize(records, seconds):
    """Per-request records -> the numbers the end-to-end metrics are made
    of. A request belongs to the sample when it was DUE inside the window
    ``[0, seconds)``; times are offsets from the window's opening."""
    sample = [r for r in records if 0.0 <= r["due"] < seconds]
    ok = [r for r in sample if not r["failed"]]
    ttft = [1e3 * (r["first"] - r["due"]) for r in ok]
    # per request: (last token time - first token time) / (tokens - 1);
    # a request whose tokens all came in one chunk has no gap to report
    tpot = [1e3 * (r["last"] - r["first"]) / (r["tokens"] - 1)
            for r in ok if r["tokens"] > 1 and r["last"] > r["first"]]
    late = [1e3 * (r["sent"] - r["due"]) for r in sample]
    # tokens the clients asked for that reached them inside the window
    useful = sum(n for r in records for t, n in r["chunks"]
                 if 0.0 <= t < seconds)
    return {"attempted": len(sample), "failed": len(sample) - len(ok),
            "ttft_ms": ttft, "tpot_ms": tpot, "late_ms": late,
            "tokens_in_window": useful,
            "tokens_per_s": useful / float(seconds)}


# -- the child process --------------------------------------------------------

class _Worker(threading.Thread):
    """One caller with one persistent connection."""

    def __init__(self, gen, index):
        super().__init__(daemon=True, name="perfbench-client-%d" % index)
        self.gen = gen
        self.client = None

    def connect(self):
        from paddle_tpu.serving import ServingClient

        if self.client is None:
            self.client = ServingClient(tuple(self.gen.address),
                                        timeout_s=self.gen.timeout_s)
        return self.client

    def request(self, idx, due):
        """Send request ``idx`` (due at offset ``due``); the stream ends
        when the client holds its target length."""
        g = self.gen
        want = int(g.plan["trg_len"][idx % len(g.plan["trg_len"])])
        src_len = int(g.plan["src_len"][idx % len(g.plan["src_len"])])
        rec = {"idx": idx, "due": due, "sent": g.now(), "first": None,
               "last": None, "tokens": 0, "src_len": src_len,
               "trg_len": want, "chunks": [], "failed": False}
        with g.lock:
            g.inflight[idx] = rec
        try:
            stream = self.connect().generate(g.source(idx, src_len),
                                             src_len=src_len)
            try:
                for ev in stream:
                    if ev.get("event") != "tokens":
                        continue
                    t = g.now()
                    n = min(len(ev["tokens"]), want - rec["tokens"])
                    rec["tokens"] += n
                    rec["chunks"].append((t, n))
                    if rec["first"] is None:
                        rec["first"] = t
                    rec["last"] = t
                    if rec["tokens"] >= want:
                        break
            finally:
                stream.close()  # in-band cancel unless the stream ended
            rec["end"] = g.now()
            if rec["tokens"] < want:
                rec["failed"], rec["error"] = True, "short stream"
        except Exception as exc:  # noqa: BLE001 - counted, not raised
            rec["failed"], rec["error"] = True, repr(exc)[:200]
            rec["end"] = g.now()
            if self.client is not None:
                self.client.close()
                self.client = None
        with g.lock:
            g.inflight.pop(idx, None)
            g.records.append(rec)


class _OpenWorker(_Worker):
    def run(self):
        while True:
            item = self.gen.todo.get()
            if item is None:
                return
            self.request(*item)


class _ClosedWorker(_Worker):
    def __init__(self, gen, index, start_at):
        super().__init__(gen, index)
        self.start_at = start_at

    def run(self):
        g = self.gen
        time.sleep(max(0.0, self.start_at - g.now()))
        while g.now() < g.plan["seconds"]:
            with g.lock:
                idx = g.next_idx
                g.next_idx += 1
            self.request(idx, g.now())


class Generator(object):
    def __init__(self, spec):
        # imported before "ready": no caller pays for it inside the run
        from paddle_tpu.serving import ServingClient  # noqa: F401

        self.spec = spec
        self.traffic = spec["traffic"]
        self.plan = make_plan(self.traffic, spec["seed"], spec["seconds"])
        self.timeout_s = float(self.traffic.get("client_timeout_s", 30.0))
        self.lock = threading.Lock()
        self.records = []
        self.inflight = {}
        self.next_idx = 0
        self.todo = queue.Queue()
        self.t_open = None
        self.address = None
        rng = np.random.RandomState((int(spec["seed"]) + 2) % (2 ** 32))
        # one pool of token ids from the seed; request i reads a slice
        self._ids = rng.randint(3, spec["vocab"],
                                size=spec["max_length"] * 64)

    def now(self):
        return time.time() - self.t_open

    def source(self, idx, src_len):
        T = self.spec["max_length"]
        row = np.zeros(T, dtype="int64")
        at = (idx * 37) % (len(self._ids) - T)
        row[:src_len] = self._ids[at:at + src_len]
        return row

    def run(self, address, t_open):
        self.address, self.t_open = address, float(t_open)
        plan, traffic = self.plan, self.traffic
        drain = float(traffic.get("drain_s", 10.0))
        if traffic["loop"] == "open":
            workers = [_OpenWorker(self, i)
                       for i in range(int(traffic["clients"]))]
            for w in workers:
                w.start()
            for idx, due in enumerate(plan["due"]):
                wait = due - self.now()
                if wait > 0:
                    time.sleep(wait)
                self.todo.put((idx, float(due)))
            for _ in workers:
                self.todo.put(None)
        else:
            n = int(traffic["clients"])
            stagger = float(traffic.get("stagger_s", 2.0))
            workers = [_ClosedWorker(self, i,
                                     -plan["ramp"] + stagger * i / n)
                       for i in range(n)]
            for w in workers:
                w.start()
        deadline = plan["seconds"] + drain
        for w in workers:
            w.join(timeout=max(0.0, deadline - self.now()))
        # whoever is still in a request when the drain ends has failed
        # (its thread is a daemon and dies with this process), and so has
        # a request that no caller was free to take
        with self.lock:
            done = list(self.records)
            for rec in self.inflight.values():
                done.append(dict(rec, failed=True, error="not finished "
                                 "%.0f s after the window" % drain,
                                 chunks=list(rec["chunks"])))
        while True:
            try:
                item = self.todo.get_nowait()
            except queue.Empty:
                break
            if item is not None:
                done.append({"idx": item[0], "due": item[1],
                             "sent": self.now(), "first": None,
                             "last": None, "tokens": 0, "chunks": [],
                             "failed": True, "error": "never sent"})
        return done


def main():
    spec = json.loads(sys.stdin.readline())
    gen = Generator(spec)
    gc.collect()
    gc.freeze()  # no full collection over the plan while callers run
    print("ready", flush=True)
    go = json.loads(sys.stdin.readline())
    records = gen.run(go["address"], go["t_open"])
    with open(spec["out"], "w") as f:
        json.dump({"records": records,
                   "planned": int(len(gen.plan["trg_len"]))}, f)
    print("done", flush=True)
    os._exit(0)  # daemon callers may still sit in a socket read


if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    main()
