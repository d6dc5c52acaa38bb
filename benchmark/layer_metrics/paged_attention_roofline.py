"""The paged-attention kernels' share of their roofline in the traced
window: the least time the chip could take for the attention the window
needed, over the time the kernels took.

The work comes from the runner's own request log, not from the program:
a token streamed at context L read the K and V of L tokens; a prefill
chunk read the prompt so far. The runner does not see prefill chunks, so
a request's ceil(prompt / chunk) chunks are spread evenly between its
submit and its first token, which is exact in a closed loop with a free
lane and off only for the few requests that straddle the window's edge.
Bytes and operations per call are `benchmark/flops.py`'s. At these
shapes (1 to 16 queries per lane against hundreds of keys, bf16) the
bound that applies is memory: about one operation per byte against the
chip's 240."""

from benchmark import flops

META = {"layer": "paged attention", "unit": "%", "better": "higher",
        "source": "device_trace", "moves": "itl_p95_ms"}

KERNELS = ("paged_attention_v",)


def lane_calls(requests, chunk, t0, t1):
    """(queries, context) of every attention call a lane made in
    [t0, t1], from the request log."""
    calls = []
    for r in requests:
        p = len(r.prompt)
        if r.stamps:
            n_chunks = -(-p // chunk)
            first = r.stamps[0]
            for k in range(n_chunks):
                t = r.t_submit + (first - r.t_submit) * (k + 1) / n_chunks
                if t0 <= t <= t1:
                    end = min((k + 1) * chunk, p)
                    calls.append((end - k * chunk, end))
        # token j (0-based) is fed back at context p + j to yield j + 1
        for j, t in enumerate(r.stamps[1:]):
            if t0 <= t <= t1:
                calls.append((1, p + j + 1))
    return calls


def read(run):
    dev = run.traced.device if run.traced is not None else None
    if dev is None or run.ctx.peaks is None:
        return None
    kernel_s = dev.kernel_s(KERNELS)
    if kernel_s <= 0:
        return None
    f = run.facts
    calls = lane_calls(run.requests, f["chunk"], run.traced.t0,
                       run.traced.t1)
    ops, nbytes = flops.paged_attention_work(
        calls, f["num_heads"], f["head_dim"], f["kv_itemsize"])
    least, bound = flops.least_time_s(ops * f["num_layers"],
                                      nbytes * f["num_layers"],
                                      run.ctx.peaks)
    run.facts["paged_attention_bound"] = bound
    return 100.0 * least / kernel_s
