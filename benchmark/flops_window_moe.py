"""Operations and bytes the window walk of the window / mixture-of-
experts family NEEDS, computed from the runner's request log: the
yardstick of `window_attention_roofline`. Like `flops.py`, nothing here
reads a trace or a clock, and nothing imports the program.
"""


def window_attention_work(lane_calls, window, heads, kv_heads, head_dim,
                          itemsize):
    """One window layer's attention over a list of (queries, context)
    lane calls (`flops.lane_calls`), where a key is visible to the query
    at position i only where 0 <= i - key < `window`. A call of c
    queries whose last sits at context L holds the queries at positions
    L - c .. L - 1.
    Operations: every query head's score and value product over the
    keys its query sees, min(i + 1, window) for the query at i: 2 x 2 x
    heads x head_dim a (query, key) pair.
    Bytes: the keys the call needs, from its first query's window to
    its last query, min(L, window + c - 1), K and V read once a KV head
    (the query heads of a group share them), 2 x kv_heads x head_dim x
    itemsize a key; plus the queries in and the outputs out a query
    head, as `flops_linear_moe.gqa_attention_work` counts them.
    Returns (flops, bytes)."""
    flops = nbytes = 0
    for c, ctx in lane_calls:
        pairs = sum(min(i + 1, window) for i in range(ctx - c, ctx))
        keys = min(ctx, window + c - 1)
        flops += 4 * pairs * heads * head_dim
        nbytes += (2 * keys * kv_heads + 2 * c * heads) * head_dim \
            * itemsize
    return flops, nbytes
