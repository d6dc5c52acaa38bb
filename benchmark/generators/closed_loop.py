"""Generator `closed_loop`: a fixed number of clients, each of which
submits its next request the moment its last one resolved, no think
time. A traffic mix that names it is a data file with

  clients         requests in flight
  distinct        how many (prompt, output) pairs the set holds
  prompt_len, output_len
                  {"median", "sigma", "min", "max"} of a log-normal
  max_total       prompt + output never exceeds it
  first_requests  {"output_len", "prompt_min", "prompt_max"}: each
                  client's first request (below)

Every seed gets the SAME sizes in the SAME order and other token ids,
so that two seeds do the same work: the lengths are the stratified
quantiles of the file's distributions, not draws from them, and the
order is fixed. (With the order seeded, a window of some 26 requests
held another subset of the sizes for every seed, and the seeds differed
by 9% in ttft_p90_ms where two runs of one seed differed by 0.2%: my
chip runs, PR 24.) The fixed order is a low-discrepancy one, so that
any run of consecutive requests, as long or short as a window happens
to hold, is spread evenly over both distributions. Nothing the program
computes depends on the ids, so the seed changes the inputs and not the
work.

Each client's first request asks for `first_requests.output_len` tokens
after a prompt whose length is spread evenly over [prompt_min,
prompt_max] across the clients: the same sizes for every seed. The load
is warm when every client's first request has resolved, so the lanes
are then at mixed phases, the warm-up is the same work in every run,
and (with one token asked for) a first request's score is the log-prob
of a single token at a known context, which the runner holds against
the reference.

All clients are played by one dispatcher thread. Every clock reading is
this file's own (`time.perf_counter`, at `submit` and in the `stream`
callback).
"""

import queue
import threading
import time
from statistics import NormalDist

import numpy as np

_SEED_MASK = 0xFFFFFFFF        # --seed may pass 2**31; numpy wants u32
_GOLDEN, _SQRT2 = 0.6180339887498949, 0.41421356237309515
_clock = time.perf_counter


def stratified_lengths(dist, n):
    """n lengths at the mid-quantiles of a clipped log-normal: the same
    for every seed."""
    nd = NormalDist()
    out = []
    for i in range(n):
        z = nd.inv_cdf((i + 0.5) / n)
        v = dist["median"] * float(np.exp(dist["sigma"] * z))
        out.append(int(min(max(round(v), dist["min"]), dist["max"])))
    return out


def spread_order(n, step=_GOLDEN):
    """A permutation of range(n) in which every run of consecutive
    entries is spread evenly over the range: the ranks of the sequence
    frac(k * step), step an irrational that rationals approximate
    badly."""
    u = (np.arange(n) * step) % 1.0
    return [int(r) for r in np.argsort(np.argsort(u))]


def length_pairs(params):
    """The fixed set of (prompt_len, output_len), in the order of one
    pass: the k-th request takes the prompt quantile of rank
    frac(k * phi) and the output quantile of rank frac(k * sqrt 2), so
    that prompts, outputs and their pairing are each spread evenly
    along the pass. No seed enters."""
    n = int(params["distinct"])
    prompts = stratified_lengths(params["prompt_len"], n)
    outputs = stratified_lengths(params["output_len"], n)
    pairs = []
    for i, j in zip(spread_order(n), spread_order(n, _SQRT2)):
        p = prompts[i]
        pairs.append((p, max(1, min(outputs[j],
                                    int(params["max_total"]) - p))))
    return pairs


def first_requests(params):
    """(prompt_len, output_len) of each client's first request."""
    f = params["first_requests"]
    lens = np.linspace(int(f["prompt_min"]), int(f["prompt_max"]),
                       int(params["clients"]))
    return [(int(round(x)), int(f["output_len"])) for x in lens]


class RequestStream:
    """An endless stream of requests: each client's first request, then
    pass after pass through the fixed set of sizes; token ids from the
    seed."""

    def __init__(self, params, seed, vocab_size):
        self.pairs = length_pairs(params)
        self.vocab_size = int(vocab_size)
        self._ids = np.random.default_rng([int(seed) & _SEED_MASK, 2])
        self._first = first_requests(params)
        self._n = 0

    def next(self):
        """(prompt_ids int32, max_new_tokens, is a first request)."""
        k, self._n = self._n, self._n + 1
        first = k < len(self._first)
        p_len, o_len = self._first[k] if first else \
            self.pairs[(k - len(self._first)) % len(self.pairs)]
        prompt = self._ids.integers(0, self.vocab_size,
                                    p_len).astype(np.int32)
        return prompt, o_len, first


class Request:
    """One request as the client saw it."""

    __slots__ = ("client", "first", "prompt", "want", "t_submit",
                 "stamps", "tokens", "t_done", "result", "error")

    def __init__(self, client, first, prompt, want):
        self.client, self.first = client, first
        self.prompt, self.want = prompt, want
        self.t_submit = self.t_done = None
        self.stamps, self.tokens = [], []
        self.result = self.error = None


class Load:
    """The clients. `submit(prompt, max_new_tokens=, stream=)` is the
    server's; every request ever submitted is in `self.log`; `self.warm`
    is set once every client's first request has resolved."""

    def __init__(self, submit, params, seed, vocab_size):
        self._submit_fn = submit
        self.clients = int(params["clients"])
        self.stream = RequestStream(params, seed, vocab_size)
        self.log = []
        self.submit_errors = 0
        self.warm = threading.Event()
        self._done = queue.SimpleQueue()
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="benchmark-clients")

    def start(self):
        self._thread.start()

    def stop(self):
        self._stop.set()
        self._thread.join(timeout=30)
        if self._thread.is_alive():
            raise RuntimeError("the client thread did not stop")

    def _submit(self, client):
        prompt, want, first = self.stream.next()
        req = Request(client, first, prompt, want)
        req.t_submit = _clock()
        self.log.append(req)

        def on_token(_rid, tok, req=req):
            req.stamps.append(_clock())
            req.tokens.append(int(tok))

        def on_done(fut, req=req):
            req.t_done = _clock()
            exc = fut.exception() if not fut.cancelled() else \
                RuntimeError("cancelled")
            if exc is None:
                req.result = fut.result()
            else:
                req.error = exc
            self._done.put(req)

        try:
            fut = self._submit_fn(prompt, max_new_tokens=want,
                                  stream=on_token)
        except (ValueError, RuntimeError) as exc:
            req.error, req.t_done = exc, _clock()
            self.submit_errors += 1
            return
        fut.add_done_callback(on_done)

    def _run(self):
        for client in range(self.clients):
            self._submit(client)
        firsts = 0
        while not self._stop.is_set():
            try:
                req = self._done.get(timeout=0.05)
            except queue.Empty:
                continue
            firsts += req.first
            if firsts == self.clients:
                self.warm.set()
            if not self._stop.is_set():
                self._submit(req.client)
