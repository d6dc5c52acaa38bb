"""Operations and bytes the power-retention kernel NEEDS, computed from
shapes and counts: the yardstick of `retention_roofline`. Like
`flops.py`, nothing here reads a trace or a clock, and nothing imports
the program.
"""


def retention_chunk_work(lane_calls, columns, heads, kv_heads, head_dim,
                         state_rows, state_itemsize, itemsize):
    """Power retention over a stretch of steps, from the program's
    counts: `lane_calls` (lane, layer) pairs that fed a valid column,
    each of which reads and writes once a lane's state (kv_heads x D x
    d) and normaliser (kv_heads x D) at `state_itemsize`, D =
    `state_rows` = d (d + 1) / 2 the pairs of the degree-2 expansion;
    `columns` valid (column, layer) pairs, each of which brings its q
    (heads x d), k and v (kv_heads x d each) at `itemsize`, and costs
    the read of the state and the normaliser by every query head (2 x
    heads x D x (d + 1)), the update by every KV head (2 x kv_heads x D
    x d) and the expansions of its q and k (heads x D + kv_heads x D).
    A lane call's intra-chunk terms (each of its c columns' query heads
    against its keys so far: 2 x heads x c (c + 1) x d) are counted at
    the calls' mean c = columns / lane_calls, which is never more than
    they need. A padded column and an idle lane are no work.
    Returns (flops, bytes)."""
    d, rows = head_dim, state_rows
    flops = columns * (2 * heads * rows * (d + 1) + 2 * kv_heads * rows * d
                       + (heads + kv_heads) * rows)
    if lane_calls:
        c = columns / lane_calls
        flops += lane_calls * 2 * heads * c * (c + 1) * d
    nbytes = (lane_calls * kv_heads * rows * (d + 1) * 2 * state_itemsize
              + columns * (heads + 2 * kv_heads) * d * itemsize)
    return flops, nbytes


def retention_counts(spans):
    """(lane calls, columns) of the retention layers, summed over the
    `serving.iteration` spans that carry the program's counts
    (`retention_lane_calls`, `retention_columns`); None where none
    does (a program without retention layers)."""
    calls = columns = 0
    seen = False
    for e in spans:
        args = e.get("args") or {}
        if e.get("name") == "serving.iteration" \
                and "retention_lane_calls" in args:
            seen = True
            calls += args["retention_lane_calls"]
            columns += args["retention_columns"]
    return (calls, columns) if seen else None
