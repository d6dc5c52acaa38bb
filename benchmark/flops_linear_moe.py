"""Operations and bytes the kernels of the linear-attention /
mixture-of-experts family NEED, computed from shapes and counts: the
yardstick of `kda_scan_roofline` and `paged_attention_gqa_roofline`.
Like `flops.py`, nothing here reads a trace or a clock, and nothing
imports the program.
"""


def kda_chunk_work(lane_calls, columns, heads, d_k, d_v, state_itemsize,
                   itemsize):
    """The gated delta rule over a stretch of steps, from the program's
    counts: `lane_calls` (lane, layer) pairs that fed a valid column,
    each of which reads and writes one state of heads x d_k x d_v at
    `state_itemsize`; `columns` valid (column, layer) pairs, each of
    which brings q, k, the decay (d_k each), v (d_v) and beta (1) in
    and takes o (d_v) out a head at `itemsize`, and costs a head the
    decay, `S'^T k`, the rank-one update and `S^T q`: 2 x 4 x d_k x
    d_v. A padded column and an idle lane are no work.
    Returns (flops, bytes)."""
    flops = columns * heads * 2 * 4 * d_k * d_v
    nbytes = (lane_calls * heads * d_k * d_v * 2 * state_itemsize
              + columns * heads * (3 * d_k + 2 * d_v + 1) * itemsize)
    return flops, nbytes


def gqa_attention_work(lane_calls, heads, kv_heads, head_dim, itemsize):
    """One layer's grouped-query paged attention over a list of
    (queries, context) lane calls (`flops.lane_calls`).
    Operations: every QUERY head's score and value product, 2 x 2 x c x
    L x heads x head_dim. Bytes: a token's K and V read once a KV head
    (the query heads of a group share them), 2 x L x kv_heads x
    head_dim x itemsize, plus the queries in and the outputs out a
    query head. Returns (flops, bytes)."""
    flops = nbytes = 0
    for c, ctx in lane_calls:
        flops += 4 * c * ctx * heads * head_dim
        nbytes += (2 * ctx * kv_heads + 2 * c * heads) * head_dim \
            * itemsize
    return flops, nbytes


def state_counts(spans):
    """(lane calls, columns) of the state layers, summed over the
    `serving.iteration` spans that carry the program's counts
    (`kda_lane_calls`, `kda_columns`); None where none does (a program
    without state layers)."""
    calls = columns = 0
    seen = False
    for e in spans:
        args = e.get("args") or {}
        if e.get("name") == "serving.iteration" \
                and "kda_lane_calls" in args:
            seen = True
            calls += args["kda_lane_calls"]
            columns += args["kda_columns"]
    return (calls, columns) if seen else None
