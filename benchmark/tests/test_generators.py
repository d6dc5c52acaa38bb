"""The traffic generators: the seed changes the inputs, not the work."""

import collections

import numpy as np

from benchmark import harness
from benchmark.generators import closed_loop, pretrain_ring

VOCAB = 50257


def _take(params, seed, n):
    s = closed_loop.RequestStream(params, seed, VOCAB)
    return [s.next() for _ in range(n)]


def _sizes(requests):
    return [(len(prompt), out) for prompt, out, _first in requests]


def test_same_seed_same_requests():
    p = harness.load_traffic("closed-16")
    a, b = _take(p, 7, 40), _take(p, 7, 40)
    assert all(np.array_equal(x[0], y[0]) and x[1:] == y[1:]
               for x, y in zip(a, b))
    c = _take(p, 8, 40)
    assert any(not np.array_equal(x[0], y[0]) for x, y in zip(a, c))
    # another seed: other ids, the same sizes in the same order
    assert _sizes(a) == _sizes(c)
    assert _sizes(a) == _sizes(_take(p, 3_000_000_000, 40))


def test_lengths_inside_their_clips_and_the_context():
    p = harness.load_traffic("closed-16")
    for prompt, out, first in _take(p, 2 ** 31 + 5, 3 * p["distinct"]):
        assert len(prompt) + out <= p["max_total"] == 1024
        assert prompt.dtype == np.int32
        assert 0 <= prompt.min() and prompt.max() < VOCAB
        if not first:
            assert p["prompt_len"]["min"] <= len(prompt) \
                <= p["prompt_len"]["max"]
            assert p["output_len"]["min"] <= out <= p["output_len"]["max"]


def test_a_pass_holds_every_size_once():
    p = harness.load_traffic("closed-16")
    n, c = p["distinct"], p["clients"]
    got = _sizes(_take(p, 1, c + 2 * n))
    assert got[c:c + n] == got[c + n:] == closed_loop.length_pairs(p)
    prompts = collections.Counter(x for x, _ in got[c:c + n])
    assert prompts == collections.Counter(
        closed_loop.stratified_lengths(p["prompt_len"], n))


def test_first_requests_are_one_token_at_spread_contexts():
    p = harness.load_traffic("closed-16")
    first = _take(p, 1, p["clients"] + 1)
    assert [f for _, _, f in first] == [True] * p["clients"] + [False]
    sizes = _sizes(first[:-1])
    assert sizes == closed_loop.first_requests(p)
    assert {o for _, o in sizes} == {1}
    lens = [x for x, _ in sizes]
    assert lens == sorted(lens) and lens[0] == 63 and lens[-1] == 1023


def test_any_run_of_consecutive_requests_is_spread_over_the_range():
    for n in (8, 24, 64):
        order = closed_loop.spread_order(n)
        assert sorted(order) == list(range(n))
        for start in range(n - n // 2):
            run = order[start:start + n // 2]
            low = sum(1 for r in run if r < n // 2)
            assert abs(low - n // 4) <= 1, (n, start, run)


def test_stratified_lengths_follow_the_distribution():
    dist = {"median": 256, "sigma": 0.6, "min": 32, "max": 768}
    got = closed_loop.stratified_lengths(dist, 64)
    assert got == sorted(got)
    assert abs(np.median(got) - 256) <= 4
    assert got[0] >= 32 and got[-1] <= 768


class _Server:
    """Resolves every request at once, on the caller's thread."""

    def __init__(self):
        self.asked = []

    def submit(self, prompt, max_new_tokens, stream):
        from concurrent.futures import Future
        self.asked.append((len(prompt), max_new_tokens))
        for t in range(max_new_tokens):
            stream(0, t)
        fut = Future()
        fut.set_result(("result", max_new_tokens))
        return fut


def test_closed_loop_is_warm_after_every_first_request():
    p = harness.load_traffic("closed-16", rehearsal=True)
    srv = _Server()
    load = closed_loop.Load(srv.submit, p, 5, 256)
    load.start()
    assert load.warm.wait(timeout=10)
    load.stop()
    c = p["clients"]
    assert srv.asked[:c] == closed_loop.first_requests(p)
    assert all(r.first for r in load.log[:c])
    done = [r for r in load.log if r.t_done is not None]
    assert len(done) > c and not any(r.first for r in load.log[c:])
    for r in done:
        assert r.error is None and len(r.stamps) == r.want == len(r.tokens)
        assert r.t_submit <= r.stamps[0] <= r.stamps[-1] <= r.t_done
    assert load.submit_errors == 0


def test_pretrain_ring_is_seeded_and_in_schema():
    p = harness.load_traffic("pretrain-seq512")
    cfg = {"max_predictions_per_seq": 80, "vocab_size": 30522,
           "type_vocab_size": 2}
    a = pretrain_ring.batches(p, cfg, 16, 3_000_000_000)
    b = pretrain_ring.batches(p, cfg, 16, 3_000_000_000)
    assert len(a) == p["ring"] == 8
    for x, y in zip(a, b):
        assert all(np.array_equal(x[k], y[k]) for k in x)
    assert not np.array_equal(a[0]["src_ids"], a[1]["src_ids"])
    f = a[0]
    assert f["src_ids"].shape == (16, 512) and f["mask_pos"].shape == (16, 80)
    assert f["mask_pos"].max() < 16 * 512
    assert f["nsp_label"].shape == (16, 1)


def test_rehearsal_overrides_apply_only_when_asked():
    assert harness.load_traffic("closed-16")["clients"] == 16
    assert harness.load_traffic("closed-16", rehearsal=True)["clients"] == 4
    assert "rehearsal" not in harness.load_traffic("closed-16")
