"""Operations and bytes the two kernels of the latent-attention /
mixture-of-experts family NEED, computed from shapes and counts: the
yardstick of `latent_attention_roofline` and `moe_experts_roofline`.
Like `flops.py`, nothing here reads a trace or a clock, and nothing
imports the program.
"""


def latent_attention_work(lane_calls, heads, row_values, value_width,
                          kv_itemsize):
    """One layer's latent paged attention (the absorbed form) over a
    list of (queries, context) lane calls (`flops.lane_calls`). A token
    is cached as ONE row of `row_values` values (`[c_kv | k_rope]`, 576)
    that every head reads, and its value is the row's first
    `value_width` (512).
    Operations: each head's score over the whole row and its sum over
    the value part, 2 x c x L x H x (row_values + value_width).
    Bytes: the L rows read ONCE for all heads, L x row_values x
    itemsize, plus the absorbed queries in and the latent outputs out.
    Lane padding of a row, padded columns and idle lanes are no work.
    Returns (flops, bytes)."""
    flops = nbytes = 0
    for c, ctx in lane_calls:
        flops += 2 * c * ctx * heads * (row_values + value_width)
        nbytes += (ctx * row_values
                   + c * heads * (row_values + value_width)) * kv_itemsize
    return flops, nbytes


def held_experts_work(assignments_held, experts_touched, hidden, inner,
                      itemsize):
    """The held experts' three products over a stretch of steps, from
    the program's routing counts: `assignments_held` tokens each through
    one expert's gate, up and down products (3 x 2 x hidden x inner),
    and the weights of each (layer, step, expert) that got a token read
    once (3 x hidden x inner x itemsize), plus each assignment's row in
    and out. An expert nobody chose is no work. Returns (flops, bytes)."""
    flops = assignments_held * 3 * 2 * hidden * inner
    nbytes = (experts_touched * 3 * hidden * inner
              + assignments_held * 2 * hidden) * itemsize
    return flops, nbytes


def routing_counts(spans):
    """(assignments, assignments_held, experts_touched) summed over the
    `serving.iteration` spans that carry the program's routing counts;
    None where none does (a program without expert layers)."""
    total = held = touched = 0
    seen = False
    for e in spans:
        args = e.get("args") or {}
        if e.get("name") == "serving.iteration" \
                and "moe_assignments_held" in args:
            seen = True
            total += args["moe_assignments"]
            held += args["moe_assignments_held"]
            touched += args["moe_experts_touched"]
    return (total, held, touched) if seen else None
