"""Operations and bytes an algorithm NEEDS, computed from shapes. The
yardstick for MFU and the roofline shares: kept here so that no PR that
claims a gain can change what a kernel is measured against.

Nothing here reads a trace or a clock, and nothing imports the program.
"""


def _numel(shape, batch):
    n = 1
    for d in shape or ():
        n *= batch if d in (-1, None) else int(d)
    return n


def program_forward_matmul_flops(program, batch):
    """Forward multiply-add operations (x2) of the matrix products of a
    `Program`, for `batch` rows: `mul`/`matmul` ops and the fused
    `multihead_attention` op (4 projections + the score and value
    products at their algorithmic T^2 cost, whatever kernel runs them).
    Only ops before the backward marker are counted, so the figure is
    the forward pass alone and holds no recompute. The arithmetic is
    `paddle_tpu/utils/model_stat.py:count_flops`'s, which `bench.py`
    uses, without its elementwise estimates. Like it, a product whose
    row count is a gathered -1 (BERT's MLM head over the 80 masked
    positions) counts one row per batch row: 1.5% of BERT-large's
    operations are left out, so the MFU reads that much low, never
    high."""
    gb = program.global_block()

    def shape(name):
        v = gb.vars.get(name)
        return None if v is None else v.shape

    total = 0
    for op in gb.ops:
        if op.type == "backward_marker":
            break
        if op.type in ("mul", "matmul"):
            xs, ys = shape(op.input("X")[0]), shape(op.input("Y")[0])
            if not xs or not ys:
                continue
            m = _numel(xs[:-1], batch)
            k = int(xs[-1])
            # matmul(transpose_y=True) contracts Y's LAST dim: the tied
            # heads (x @ emb.T). Y is (n, k) there.
            t_y = bool(op.attrs.get("transpose_Y", False))
            n = int(ys[-2] if t_y else ys[-1])
            total += 2 * m * k * n
        elif op.type == "multihead_attention":
            qs = shape(op.input("Query")[0])
            t, m = int(qs[-2]), int(qs[-1])
            b = batch if qs[0] in (-1, None) else int(qs[0])
            total += b * (4 * 2 * t * m * m + 2 * 2 * t * t * m)
    return total


def least_time_s(flops, nbytes, peaks):
    """The least time the chip could take: the larger of operations over
    peak FLOP/s and bytes over peak bytes/s. Returns (seconds, bound),
    bound being "compute" or "memory"."""
    t_c = flops / peaks["flops_per_s"]
    t_m = nbytes / peaks["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")


def lane_calls(requests, chunk, t0, t1):
    """(queries, context) of every attention call a lane made in
    [t0, t1], from a runner's request log (`prompt`, `t_submit`,
    `stamps`): a token streamed at context L read the K and V of L
    tokens; a prefill chunk read the prompt so far. The runner does not
    see prefill chunks, so a request's ceil(prompt / chunk) chunks are
    spread evenly between its submit and its first token, which is exact
    in a closed loop with a free lane and off only for the few requests
    that straddle the window's edge."""
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


def decoder_step_flops(calls, sampled, body_per_token, head_per_token,
                       layers, heads, head_dim):
    """Forward operations a decoder NEEDS for the attention calls of
    `lane_calls` and `sampled` sampled tokens: every fed token through
    the layers' matrix products (`body_per_token`), every sampled token
    through the head (`head_per_token`), and each call's score and value
    products at its live context. Padded columns, idle lanes and a head
    computed for columns that sample nothing are no work."""
    fed = sum(c for c, _ctx in calls)
    attention, _bytes = paged_attention_work(calls, heads, head_dim, 0)
    return (fed * body_per_token + sampled * head_per_token
            + layers * attention)


def paged_attention_work(lane_calls, heads, head_dim, kv_itemsize):
    """One layer's paged attention over a list of (queries, context)
    lane calls: a decode token at context L is (1, L); a prefill chunk
    of c tokens whose last token sits at context L is (c, L).
    Operations: the score and the value product, 2 x 2 x c x L x H x D.
    Bytes: K and V of the lane's LIVE context read once, L x H x D x 2
    x itemsize, plus the queries in and the outputs out. Padded columns
    and idle lanes are no work. Returns (flops, bytes)."""
    flops = nbytes = 0
    hd = heads * head_dim
    for c, ctx in lane_calls:
        flops += 4 * c * ctx * hd
        nbytes += 2 * ctx * hd * kv_itemsize + 2 * c * hd * kv_itemsize
    return flops, nbytes


# matrix products each flash kernel call must make, given that the
# probabilities are never stored (so the backward kernels recompute the
# scores): fwd S, PV; dq S, dP, dQ; dkv S, dP, dV, dK
FLASH_PRODUCTS = {"flash_fwd": 2, "flash_dq": 3, "flash_dkv": 4}
# (B, H, T, D) tensors each call must read and write: fwd q k v -> o;
# dq q k v do o -> dq; dkv q k v do o -> dk dv. The (B, H, T) row
# statistics are left out (D times smaller).
FLASH_TENSORS = {"flash_fwd": 4, "flash_dq": 6, "flash_dkv": 7}


def flash_work(kernel, b, h, t, d, itemsize, causal=False):
    """(flops, bytes) one call of `kernel` needs at (B, H, T, D)."""
    per_product = 2 * b * h * t * t * d
    if causal:
        per_product //= 2
    return (FLASH_PRODUCTS[kernel] * per_product,
            FLASH_TENSORS[kernel] * b * h * t * d * itemsize)
