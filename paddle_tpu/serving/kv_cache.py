"""Paged KV cache: a block-pooled KV store with per-request block tables.

The dense serving cache (`inference/decoding.init_kv_cache`) reserves
(B, H, T_max, D) per lane — every request pays for the longest request's
worst case, and a new batch shape means a new executable. The paged
layout (PAPERS.md "Ragged Paged Attention") pools KV in fixed-size
blocks instead:

    per layer:  kv_pool      : (num_blocks, H, block_size, 2*D) — the
                 K row of a token in lanes [0, D), its V row in
                 [D, 2*D) (`fuse_kv` / `split_kv`)
    per request: block_table : (max_blocks,) int32 — logical position
                 p lives in pool block table[p // block_size] at row
                 p % block_size.

K and V of a block lie side by side in ONE array, under the key "kv" of
the layer's dict, because of how a TPU keeps arrays: a minor dim of 64
(GPT-2's head_dim) would be padded to the 128 lanes of a tile, so the
compiler kept a (N, H, bs, 64) pool with the BLOCK dim minor, and every
step re-laid each pool out into the row-major form the Pallas kernels
read and back again: 62 of a 104 ms step (PERF.md section 6, PR 26 and
PR 29). With 2*D = 128 in the minor dim the device's own layout is the
kernels', the bytes are the same, and a step writes a layer's K and V
with one plan, one read of the touched blocks and one scatter. It is
the only layout, whatever the head_dim.

Requests of wildly different lengths then share ONE pool (and one
compiled step): length is data (positions + tables), never shape. Block
0 is the reserved NULL block — table padding and masked-token writes
land there, and the attention mask guarantees it is never read.

`paged_attention` is the op's dispatcher: by default it routes to a
Pallas ragged paged attention kernel (`ops/pallas/paged.py` — the table
walk fused into the kernel, a grid step a live group of 128 key
positions of a lane and no step past a lane's true length,
bf16 KV with f32 accumulation), falling back to
`paged_attention_reference`, the pure-JAX semantic spec (gather blocks
by table -> masked attention) that kernel v1 is pinned bitwise against
in interpret mode (on the lane's table cut to its live groups). Two kernel generations exist: v1 (gather the live
blocks to VMEM, then the reference math — bitwise-stable, VMEM scales
with the table width) and v2 (a walk of groups of 128 keys with an
online softmax — O(one group) of VMEM whatever the table width). Auto
mode picks v1 while its scratch fits the VMEM ceiling and v2 past it;
`PADDLE_TPU_PAGED_KERNEL` (0/1/auto/v1/v2) overrides the routing;
everything above the op (scheduler, engine) is kernel-agnostic.

Grouped-query attention (ISSUE 16): ``PagedKVCache(num_kv_heads=)``
shrinks the pools to (num_blocks, H_kv, block_size, 2*D) with
H % H_kv == 0; query head j attends KV head j // (H/H_kv) (the
contiguous-group convention). Every byte count — pool_bytes, shard
bytes, ledger rows, handoff transfers — divides by the group factor,
compounding with int8 quantization.

Latent pools (ISSUE 34): a layer of multi-head LATENT attention caches
one row a token, `[c_kv | k_rope]` padded to whole lane tiles
(`ops/pallas/paged.latent_row_width`), that every head reads, so its
pool is (num_blocks, 1, block_size, W): the head axis is kept at
length one so that every block-addressed rewriter here (the write, COW,
the wire, the host tier) is the same code. `PagedKVCache(geometry=)`
takes the (rows, width) of each layer's pool; `paged_latent_attention`
is that layer's dispatcher, as `paged_attention` is a K-beside-V
layer's, and counts into the same dispatch accounting.

State layers (ISSUE 36): a layer of linear attention (the gated delta
rule, `ops/pallas/linear.py`) has no row a token. It keeps, for each
LANE, one float32 state a head and the last rows of its short
convolution's input, and `geometry` says so with a dict of the arrays
a lane holds in place of a block's (rows, width). Such a layer's entry
of `pools` is `{"state", "conv"}` with a leading lane axis of
`num_slots`: the same donated list, but nothing the allocator, the
tables, the refcounts, COW, the wire or the host tier address, so every
block rewriter here REFUSES a cache that has one (ROADMAP R5 says what
each would need), and the byte counts add the lanes' arrays.
`kda_chunk` is that layer's dispatcher, counted like the paged ones.
A power-retention layer (`ops/pallas/retention.py`) is a state layer
too, `{"state", "norm"}` a lane, and `power_retention_chunk` its
dispatcher. A model may have no paged layer at all: the allocator then
counts positions into blocks that no pool holds, and admission, the
tables and the donation of `pools` are the same code.

Window rings: a layer of sliding-window attention reads the last W
positions of a lane and no others, so its keys and values live in a
ring of R blocks the lane owns, R * bs >= W + C + bs - 2
(`ring_blocks_for`), and not in blocks from the allocator. A step
derives the ring's table from lane and position (`ring_table`), writes
through it as any layer writes through its table, and walks it with
`paged_attention(window=)`, which starts at the window's first block. A
new request on a lane reads only the positions it has written: the
walk never reaches past the lane's own positions, whatever the ring
held before. Like a state, a ring is refused by every block rewriter.

`PagedDecodeLayer` adapts a layer's pool to the dense mapping
interface `decoding.py` step_fns consume (`cache[i]["k"]`,
`update_kv_cache`), so an existing step_fn decodes against either cache
unchanged. Beam search runs paged too (ISSUE 20): the serving engine's
request groups reorder beams by remapping block TABLES host-side —
`fork_table` + `cow_copy` at divergence sites — instead of
`_gather_beams`'s dense leading-dim gather, so the adapter exists for
step_fn parity harnesses, not as a beam crutch.

Cross-request block sharing (ISSUE 10): every allocated block carries a
host-side refcount. The prefix cache (serving/prefix_cache.py) refs a
block it indexes and every request using a shared block refs it too;
`unref` hands a block back to the free list only when the LAST
reference drops, and `free` (the raw single-owner API) refuses both a
double free and a free of a block somebody else still references —
with refcounts in play a silent double free would hand one block to
two requests and corrupt both. `cow_copy` is the copy-on-write
primitive: copy one block's rows to a fresh block in every pool (and
every attached sibling cache — the speculative-decoding draft pools
share block ids) so the writer's table can be repointed while readers
keep the original.

Quantized pools (ISSUE 14): ``PagedKVCache(kv_dtype="int8")`` stores
the block pools as int8 with per-block-row, per-head f32 scales in two
PARALLEL pools of shape (num_blocks, H, block_size), "k_scale" and
"v_scale", beside the (num_blocks, H, block_size, 2*D) code pool. The write path quantizes
(symmetric absmax over D, one scale per written token row per head —
a full-block scale would force requantizing every resident row on
every incremental write, which doubles write traffic and compounds
rounding error); the read path dequantizes — in the Pallas kernel the
int8 blocks are what the DMA copies, so decode HBM traffic drops ~2x
on top of the capacity win. Scales ride block ids everywhere blocks
do: `cow_copy`, `adopt_block_from`, and the prefix-cache chain index
address pools BY BLOCK ID, so sharing, fleet handoff, and sibling
draft pools compose with quantization without carrying any extra
state. Score/softmax accumulation stays f32; the dequantized compute
dtype follows the query dtype (the model's activation dtype).
"""

import os
import threading

import numpy as np

import jax
import jax.numpy as jnp

__all__ = ["PagedKVCache", "HostKVTier", "PagedDecodeLayer",
           "paged_attention", "fuse_kv", "split_kv", "KV_LAYOUT",
           "paged_attention_reference", "gather_block_kv",
           "paged_latent_attention", "paged_latent_attention_reference",
           "ring_table", "ring_blocks_for",
           "lane_owned", "kda_chunk", "power_retention_chunk",
           "gather_block_kv_pair", "gather_block_scales",
           "build_paged_decode_cache", "quantize_kv_rows",
           "write_block_kv_quant",
           "NULL_BLOCK", "paged_kernel_mode", "paged_kernel_supported",
           "kernel_dispatch_stats"]

NULL_BLOCK = 0          # reserved: never allocated, never attended
NEG_INF = -1e9
KV_QMAX = 127.0         # symmetric int8 range; -128 is never produced,
                        # so negation stays exact under quantization

# Trace-time dispatch accounting (flash.py's TRACE_COUNT idiom): how
# many paged_attention dispatches routed to the Pallas kernel vs the
# pure-JAX reference. The engine and its tests assert engagement off these
# so a silent fallback can never masquerade as a kernel win.
# FALLBACK_REASONS mirrors the `serving.kernel.fallback{reason=...}`
# labeled series so tests and get_stats can tell a deliberate pin
# (pinned_off) from a degradation (unsupported).
KERNEL_DISPATCHES = 0
FALLBACK_DISPATCHES = 0
FALLBACK_REASONS = {}
# which kernel generation each kernel dispatch took ({"v1": n, "v2": n})
# — the engine's get_stats()["kernel"]["version"] reads the delta
# across its first trace, mirroring serving.kernel.version
KERNEL_VERSIONS = {}
# and which kernel, by its name in a device trace ("paged_attention_v1",
# "paged_attention_v2", "paged_latent_attention"): a generation says how
# a walk holds its state, and the latent walk shares v2's
KERNEL_NAMES = {}

# v1 gathers a lane's whole table into VMEM: M blocks x H_kv x bs x
# 2*D in the pool's dtype (f32 for int8 pools). Auto mode streams
# through v2 once that passes this ceiling — env-overridable so tests
# (and unusual VMEM budgets) can move it.
V2_AUTO_VMEM_BYTES = 8 * 1024 * 1024

# what a wire payload says of its blocks' layout (wire_geometry): a
# peer whose pools are the older {"k", "v"} pair of (N, H, bs, D) arrays
# sends no such word, and deserialize_block refuses it unread
KV_LAYOUT = "kv_side_by_side"


# ---------------------------------------------------------------------------
# functional ops (jit-traceable; the Pallas kernel contract)
# ---------------------------------------------------------------------------

def fuse_kv(k, v):
    """K rows (..., D) and V rows (..., D) side by side, (..., 2*D): the
    layout of a pool's minor dim, and of what a step writes into it."""
    return jnp.concatenate([k, v], axis=-1)


def split_kv(kv):
    """(..., 2*D) -> (K (..., D), V (..., D)): two slices of the minor
    dim, the inverse of fuse_kv."""
    d = kv.shape[-1] // 2
    return kv[..., :d], kv[..., d:]


def gather_block_kv_pair(kv_pool, block_table):
    """Gather a fused pool dense, one indexed pass for K and V both,
    and split it: -> (K, V), each (B, H, M*bs, D).
    The dense materialization is the reference's inherent O(M*bs) HBM
    cost per lane per step — every decode iteration copies each
    request's FULL table width regardless of its true length. That is
    the traffic the Pallas kernel (ops/pallas/paged.py) is built to
    remove by walking only each lane's live groups in-kernel (PERF.md
    section 6, PR 31)."""
    return split_kv(gather_block_kv(kv_pool, block_table))


def gather_block_kv(pool, block_table):
    """pool (N, H, bs, W) gathered by table (B, M) -> dense
    (B, H, M*bs, W) view in logical-position order."""
    b, m = block_table.shape
    n, h, bs, d = pool.shape
    g = jnp.take(pool, block_table.reshape(-1), axis=0)
    g = g.reshape(b, m, h, bs, d)
    return jnp.moveaxis(g, 2, 1).reshape(b, h, m * bs, d)


def gather_block_scales(scale_pool, block_table):
    """scale pool (N, H, bs) gathered by table (B, M) -> dense
    (B, H, M*bs) f32 view aligned with gather_block_kv's rows."""
    b, m = block_table.shape
    n, h, bs = scale_pool.shape
    g = jnp.take(scale_pool, block_table.reshape(-1), axis=0)
    g = g.reshape(b, m, h, bs)
    return jnp.moveaxis(g, 2, 1).reshape(b, h, m * bs)


def quantize_kv_rows(vals):
    """Symmetric absmax int8 quantization over the LAST axis: one f32
    scale per leading-index row. vals (..., D) float ->
    (int8 (..., D), f32 scales (...)). An all-zero row gets scale 1.0
    (not 0 — dequant must not produce NaN via 0 * inf or 0/0 paths),
    and quantizes to exact zeros either way."""
    v = vals.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(v), axis=-1)
    scale = jnp.where(absmax > 0, absmax / KV_QMAX, 1.0)
    q = jnp.clip(jnp.round(v / scale[..., None]), -KV_QMAX, KV_QMAX)
    return q.astype(jnp.int8), scale


def paged_attention_reference(q, kv_pool, block_table, q_positions,
                              k_scale=None, v_scale=None, window=None):
    """Pure-JAX paged attention: gather blocks by table, mask keys
    beyond each query's position, softmax in f32, weighted sum.

    q:           (B, H, C, D) — C query tokens per request lane
    kv_pool:     (N, H, bs, 2*D), K beside V (split_kv)
    block_table: (B, M) int32
    q_positions: (B, C) int32 — logical position of each query token
    k/v_scale:   (N, H, bs) f32 per-row scales — REQUIRED for int8
                 pools, absent otherwise
    returns      (B, H, C, D) in the pool's dtype (int8 pools: in q's
                 dtype — the model's activation dtype)

    The numerics deliberately mirror the dense cache path in
    models/gpt.build_kv_step: scores and softmax in f32, probabilities
    cast back to the value dtype before the PV contraction — so a paged
    decode is bitwise-comparable to the dense one. This body is the
    SEMANTIC SPEC for the Pallas kernel: ops/pallas/paged.py walks the
    table in-kernel instead of materializing the dense gather and is
    pinned bitwise against this function for f32 AND int8 pools in
    interpret mode (tests/ops/test_paged_kernel.py). The int8 branch
    dequantizes the gathered rows (int8 -> f32 multiply by the row
    scale) exactly where the kernel dequantizes its VMEM-resident
    gather: keys straight into the f32 score math, values cast to the
    compute dtype the probabilities use.

    Grouped-query attention: pools with H_kv < H heads (H % H_kv == 0)
    are gathered (and, for int8, dequantized) at H_kv and then
    REPEATED across each query-head group — pure copies, so this is
    bitwise-identical to running the dense math against a pool that
    physically stored each KV head H/H_kv times (the repeat-KV
    equivalence the GQA tests pin).

    `window` W (sliding-window attention, the window walk's spec): a
    key is visible to a query at position i only where
    i - key < W as well, which hides whatever a table column below the
    window holds (a ring block since rewritten)."""
    d = q.shape[-1]
    h, hp = q.shape[1], kv_pool.shape[1]
    if hp > h or h % hp or kv_pool.shape[3] != 2 * d:
        raise ValueError(
            f"pool {kv_pool.shape} and q {q.shape} do not match (a fused "
            f"pool is (N, H_kv, bs, 2 * head_dim); GQA needs q heads a "
            f"multiple of pool heads)")
    rep = h // hp
    if kv_pool.dtype != jnp.int8 and (k_scale is not None
                                      or v_scale is not None):
        # same guard as the kernel entry point, so the error does not
        # depend on WHICH path the dispatcher happened to take (a
        # PADDLE_TPU_PAGED_KERNEL=0 dev loop must not silently drop
        # scales a TPU run would reject)
        raise ValueError(
            f"scale pools passed with a non-int8 pool ({kv_pool.dtype}) "
            f"— scales only mean something for quantized KV")
    if kv_pool.dtype == jnp.int8:
        if k_scale is None or v_scale is None:
            raise ValueError(
                "int8 pools need k_scale/v_scale (the per-row f32 "
                "scale pools stored beside the blocks)")
        cdt = q.dtype
        gkq, gvq = gather_block_kv_pair(kv_pool, block_table)
        gks = gather_block_scales(k_scale, block_table)
        gvs = gather_block_scales(v_scale, block_table)
        gk = gkq.astype(jnp.float32) * gks[..., None]
        gv = (gvq.astype(jnp.float32) * gvs[..., None]).astype(cdt)
        if rep > 1:
            gk = jnp.repeat(gk, rep, axis=1)
            gv = jnp.repeat(gv, rep, axis=1)
        s = jnp.einsum("bhcd,bhtd->bhct", q.astype(jnp.float32),
                       gk) / np.sqrt(d)
        mask = _visible(gk.shape[2], q_positions, window)
        s = jnp.where(mask, s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1).astype(gv.dtype)
        return jnp.einsum("bhct,bhtd->bhcd", p, gv)
    gk, gv = gather_block_kv_pair(kv_pool, block_table)
    if rep > 1:
        gk = jnp.repeat(gk, rep, axis=1)
        gv = jnp.repeat(gv, rep, axis=1)
    s = jnp.einsum("bhcd,bhtd->bhct", q, gk) / np.sqrt(d)
    mask = _visible(gk.shape[2], q_positions, window)
    s = jnp.where(mask, s.astype(jnp.float32), NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(gv.dtype)
    return jnp.einsum("bhct,bhtd->bhcd", p, gv)


def _visible(t, q_positions, window):
    """(B, 1, C, T) bool: key position k is visible to the query at
    position i where k <= i (and i - k < window, where one is given)."""
    key_pos = jnp.arange(t)[None, None, None, :]
    q_pos = q_positions[:, None, :, None]
    mask = key_pos <= q_pos
    if window is not None:
        mask &= q_pos - key_pos < window
    return mask


def ring_blocks_for(window, chunk, block_size):
    """Blocks a lane's ring must hold for a window layer: a step's walk
    reads from the block holding its first query's position less
    window - 1 to the block holding its last column, at most
    ceil((window + chunk + block_size - 2) / block_size) of them, which
    must all be distinct blocks of the ring; rounded up to whole walk
    groups (`ops/pallas/paged.walk_group`). 4096 / 16 / 16 -> 264."""
    from ..ops.pallas.paged import walk_group
    need = -(-(int(window) + int(chunk) + int(block_size) - 2)
             // int(block_size))
    p = walk_group(block_size, need)
    return -(-need // p) * p


def ring_table(tables, ring_blocks):
    """A window layer's table, derived from lane and position: column j
    of lane s names block 1 + s * R + (j mod R) of the ring pool (block
    0 is NULL), wherever the lane's own table holds a block there, and
    NULL where it holds none (an idle lane, columns past the lane's
    reservation). tables (S, M) int32 -> (S, M) int32."""
    s, m = tables.shape
    lane = jax.lax.broadcasted_iota(jnp.int32, (s, m), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (s, m), 1)
    return jnp.where(tables != NULL_BLOCK,
                     1 + lane * ring_blocks + col % ring_blocks,
                     NULL_BLOCK).astype(jnp.int32)


def paged_kernel_mode():
    """Resolve PADDLE_TPU_PAGED_KERNEL ->
    'off' | 'force' | 'auto' | 'v1' | 'v2'.
    Unset/'auto': use a kernel whenever the operands qualify (the
    default — tier-1 exercises the real kernels under the Pallas
    interpreter on CPU), choosing v1 while its full-table VMEM gather
    fits the ceiling and the streaming v2 past it. '0' pins the
    reference path; '1' demands a kernel (same v1/v2 choice as auto)
    and raises on unsupported operands instead of silently degrading;
    'v1'/'v2' pin the kernel GENERATION (degrading to the reference,
    with a labeled fallback, when operands do not qualify)."""
    raw = os.environ.get("PADDLE_TPU_PAGED_KERNEL", "auto").lower()
    if raw in ("0", "off", "false"):
        return "off"
    if raw in ("1", "force", "true"):
        return "force"
    if raw in ("auto", ""):
        return "auto"
    if raw in ("v1", "v2"):
        return raw
    raise ValueError(
        f"PADDLE_TPU_PAGED_KERNEL={raw!r}: expected 0, 1, auto, v1 "
        f"or v2")


def _v1_scratch_bytes(kv_pool, block_table):
    """v1's VMEM scratch footprint for these operands: the lane's K and
    V side by side at full table width, as the kernel allocates it."""
    from ..ops.pallas.paged import v1_scratch_bytes
    n, hp, bs, d2 = kv_pool.shape
    return v1_scratch_bytes(hp, bs, d2 // 2, block_table.shape[1],
                            kv_pool.dtype)


def _v2_auto_vmem_bytes():
    raw = os.environ.get("PADDLE_TPU_PAGED_V2_AUTO_BYTES")
    return int(raw) if raw else V2_AUTO_VMEM_BYTES


def _kernel_version_for(mode, kv_pool, block_table):
    """Which kernel generation a kernel-bound dispatch takes. Explicit
    'v1'/'v2' modes pin it; 'auto'/'force' keep the bitwise-stable v1
    while its table-wide gather fits the VMEM ceiling and stream via
    v2 past it (the whole point of v2: context length stops being a
    VMEM problem)."""
    if mode in ("v1", "v2"):
        return mode
    return ("v2" if _v1_scratch_bytes(kv_pool, block_table)
            > _v2_auto_vmem_bytes() else "v1")


def paged_kernel_supported(q, kv_pool, k_scale=None, v_scale=None,
                           latent=False):
    """Shapes/dtypes the kernels handle: 4-D operands with an f32 or
    bf16 fused pool (N, H_kv, bs, 2 * q's head_dim) — pool heads equal
    to q's heads (MHA) or an exact divisor (GQA) — or an int8 pool
    accompanied by its two (N, H_kv, bs) f32 scale pools (quantized
    serving — the kernels fuse the dequant into the gather). With
    `latent`, the latent walk's geometry: an f32 or bf16 pool
    (N, 1, bs, W), one row a token, against absorbed queries
    (B, C, H, W) of the same width, and no scale pools (a latent row
    has no K half and V half to scale apart)."""
    if q.ndim != 4 or kv_pool.ndim != 4:
        return False
    if latent:
        return (kv_pool.shape[1] == 1 and q.shape[3] == kv_pool.shape[3]
                and k_scale is None and v_scale is None
                and kv_pool.dtype in (jnp.float32, jnp.bfloat16))
    h, hp = q.shape[1], kv_pool.shape[1]
    if hp > h or h % hp or kv_pool.shape[3] != 2 * q.shape[3]:
        return False
    if kv_pool.dtype == jnp.int8:
        return all(s is not None and s.shape == kv_pool.shape[:3]
                   and s.dtype == jnp.float32
                   for s in (k_scale, v_scale))
    return kv_pool.dtype in (jnp.float32, jnp.bfloat16)


def _record_dispatch(kernel, reason=None, version=None, name=None):
    """Trace-time metrics: dispatch counters + the interpret-mode gauge
    land in the global registry so GenerationServer.get_stats() and the
    trace_report serving summary can prove the kernel engaged.
    Fallbacks carry a `reason` label (pinned_off / unsupported /
    unsupported_under_shard_map) on top of the unlabeled
    aggregate, so a dashboard can tell an operator pin from a silent
    degradation. Kernel dispatches carry the kernel GENERATION: a
    `version` label on `serving.kernel.traced` (and "reference" on the
    fallback series), plus the `serving.kernel.version` gauge (1 = v1,
    2 = v2, 0 = last dispatch fell back)."""
    global KERNEL_DISPATCHES, FALLBACK_DISPATCHES
    from ..observability import _help
    from ..observability.metrics import global_registry
    reg = global_registry()
    vgauge = reg.gauge("serving.kernel.version",
                       _help("serving.kernel.version"))
    if kernel:
        KERNEL_DISPATCHES += 1
        c = reg.counter("serving.kernel.traced",
                        _help("serving.kernel.traced"))
        c.inc()                             # unlabeled aggregate
        if version or not name:
            # a walk over a block table has a generation; a kernel that
            # walks none (`kda_chunk`), or whose generation its name
            # fixes (the window walk), by its name alone
            version = version or "v1"
            KERNEL_VERSIONS[version] = KERNEL_VERSIONS.get(version,
                                                           0) + 1
            c.labels(version=version).inc()     # per-generation series
            vgauge.set(2 if version == "v2" else 1)
        name = name or "paged_attention_" + version
        KERNEL_NAMES[name] = KERNEL_NAMES.get(name, 0) + 1
        from ..ops.pallas import paged as _paged
        reg.gauge("serving.kernel.interpret",
                  _help("serving.kernel.interpret")).set(
                      1 if _paged._interpret() else 0)
    else:
        FALLBACK_DISPATCHES += 1
        reason = reason or "unsupported"
        FALLBACK_REASONS[reason] = FALLBACK_REASONS.get(reason, 0) + 1
        c = reg.counter("serving.kernel.fallback",
                        _help("serving.kernel.fallback"))
        c.inc()                             # unlabeled aggregate
        c.labels(reason=reason).inc()       # per-reason series
        c.labels(version="reference").inc()
        vgauge.set(0)


def kernel_dispatch_stats():
    """Module-level dispatch counters as a dict (tests read it)."""
    return {"kernel_dispatches": KERNEL_DISPATCHES,
            "fallback_dispatches": FALLBACK_DISPATCHES,
            "fallback_reasons": dict(FALLBACK_REASONS),
            "kernel_versions": dict(KERNEL_VERSIONS),
            "kernel_names": dict(KERNEL_NAMES),
            "mode": paged_kernel_mode()}


def _dispatch(supported, operands, reference, kernel,
              in_shard_map=False):
    """The ladder every paged dispatcher walks, at trace time: the
    reference where the operator pinned it (mode off) or the operands
    do not qualify (force mode raises instead, except under a
    shard_map), each a labeled fallback; else the kernel, counted with
    its generation and name. `kernel(mode)` gives (generation or None
    for a kernel that walks no table, name or None for
    "paged_attention_<generation>", the call); `operands` is what the
    refusal says of them."""
    mode = paged_kernel_mode()
    if mode != "off" and supported:
        version, name, call = kernel(mode)
        _record_dispatch(kernel=True, version=version, name=name)
        return call()
    if mode == "force" and not in_shard_map:
        raise ValueError("PADDLE_TPU_PAGED_KERNEL=1 but operands do not "
                         "qualify " + operands)
    _record_dispatch(kernel=False, reason=(
        "pinned_off" if mode == "off" else
        "unsupported_under_shard_map" if in_shard_map else "unsupported"))
    return reference()


def paged_attention(q, kv_pool, block_table, q_positions,
                    k_scale=None, v_scale=None, *, in_shard_map=False,
                    window=None):
    """Paged attention dispatcher — the frozen serving contract.

    Routes to the Pallas ragged paged attention kernel
    (ops/pallas/paged.ragged_paged_attention: in-kernel table walk,
    per-lane early stop, NULL block never read, bf16 KV with f32
    accumulation, int8 KV with the dequant fused into the VMEM gather)
    whenever `PADDLE_TPU_PAGED_KERNEL` allows it and the operands
    qualify. `paged_attention_reference`, the documented pure-JAX spec,
    runs only when the operator pinned it (mode off) or the operands do
    not qualify — each with a labeled `serving.kernel.fallback` reason.
    The pool is the fused (N, H_kv, bs, 2*D) array, K beside V. int8
    pools ride the SAME auto mode: the scale pools travel as two extra
    operands and the decision happens at TRACE time
    (shapes/dtypes are static under jit), so a compiled fused step pays
    zero dispatch overhead.

    `in_shard_map` is a fact only the caller has: the tensor-parallel
    fused step (GPTServingModel.build_fused_step) wraps this body in a
    shard_map and says so. There, force mode with non-qualifying
    operands falls back (reason unsupported_under_shard_map) instead of
    raising — a ValueError mid-shard_map-trace surfaces as transform
    internals, not as this dispatcher's message. Plain force-mode
    misuse still raises loudly.

    `window` W (a window layer: a key visible only where 0 <= i - key
    < W) routes to v2's walk from the window's first group, traced as
    `paged_window_attention`, or to the reference with the window's
    mask; `block_table` is then the lane's ring (`ring_table`). That
    walk's generation is fixed, so it counts under its name alone
    (`KERNEL_NAMES`), as `kda_chunk` does."""
    def reference():
        return paged_attention_reference(q, kv_pool, block_table,
                                         q_positions, k_scale, v_scale,
                                         window=window)

    def kernel(mode):
        from ..ops.pallas.paged import (ragged_paged_attention,
                                        ragged_paged_attention_v2)
        if window is not None:
            return None, "paged_window_attention", lambda: \
                ragged_paged_attention_v2(q, kv_pool, block_table,
                                          q_positions, k_scale=k_scale,
                                          v_scale=v_scale, window=window)
        version = _kernel_version_for(mode, kv_pool, block_table)
        fn = (ragged_paged_attention_v2 if version == "v2"
              else ragged_paged_attention)
        return version, None, lambda: fn(
            q, kv_pool, block_table, q_positions, k_scale=k_scale,
            v_scale=v_scale)

    return _dispatch(
        paged_kernel_supported(q, kv_pool, k_scale, v_scale),
        f"(q {q.shape} {q.dtype}, pool {kv_pool.shape} {kv_pool.dtype}, "
        f"scales {'present' if k_scale is not None else 'absent'})",
        reference, kernel, in_shard_map)


def paged_latent_attention_reference(q, kv_pool, block_table,
                                     q_positions, *, value_width, scale):
    """Pure-JAX latent paged attention, the semantic spec of
    `ops/pallas/paged.paged_latent_attention`: gather the lanes' rows
    by table, score every head's absorbed query against the whole row,
    mask keys past each query's position, softmax in f32, and sum the
    rows' first `value_width` lanes.

    q (B, C, H, W); kv_pool (N, 1, bs, W); block_table (B, M);
    q_positions (B, C) -> (B, C, H, value_width) in the pool's dtype."""
    g = gather_block_kv(kv_pool, block_table)[:, 0]         # (B, T, W)
    s = jnp.einsum("bchw,btw->bcht", q.astype(jnp.float32),
                   g.astype(jnp.float32)) * scale
    key_pos = jnp.arange(g.shape[1])
    mask = key_pos[None, None, None, :] <= q_positions[:, :, None, None]
    p = jax.nn.softmax(jnp.where(mask, s, NEG_INF), axis=-1)
    return jnp.einsum("bcht,btv->bchv", p.astype(g.dtype),
                      g[..., :value_width])


def paged_latent_attention(q, kv_pool, block_table, q_positions, *,
                           value_width, scale):
    """`paged_attention`'s sibling for a latent pool (N, 1, bs, W): the
    same modes, the same labeled fallbacks, the same dispatch counters.
    The latent walk streams its groups through an online softmax, so it
    counts as kernel generation "v2" (VMEM independent of the table
    width, allclose and not bitwise its reference) under a name of its
    own, "paged_latent_attention" (`KERNEL_NAMES`,
    `get_stats()["kernel"]["name"]`)."""
    def reference():
        return paged_latent_attention_reference(
            q, kv_pool, block_table, q_positions,
            value_width=value_width, scale=scale)

    def kernel(mode):
        from ..ops.pallas.paged import paged_latent_attention as walk
        return "v2", "paged_latent_attention", lambda: walk(
            q, kv_pool, block_table, q_positions,
            value_width=value_width, scale=scale)

    return _dispatch(
        paged_kernel_supported(q, kv_pool, latent=True),
        f"for the latent walk (q {q.shape} {q.dtype}, pool "
        f"{kv_pool.shape} {kv_pool.dtype})", reference, kernel)


def kda_chunk(q, k, v, g, beta, state, counts, reset):
    """A state layer's dispatcher: one chunk of every lane's columns
    against the lane's carried state (`ops/pallas/linear.kda_chunk`,
    whose docstring has the shapes), through the same modes, labeled
    fallbacks and dispatch counters as the paged walks. The kernel
    walks no block table, so it has no generation: it counts under its
    name, "kda_chunk", alone. Its reference is the same chunk in plain
    `jax.numpy`."""
    from ..ops.pallas import linear

    def reference():
        return linear.kda_chunk_reference(q, k, v, g, beta, state,
                                          counts, reset)

    def kernel(mode):
        return None, "kda_chunk", lambda: linear.kda_chunk(
            q, k, v, g, beta, state, counts, reset)

    return _dispatch(
        q.ndim == 4 and state.ndim == 4
        and state.dtype == jnp.float32
        and q.dtype in (jnp.float32, jnp.bfloat16),
        f"for the chunked delta rule (q {q.shape} {q.dtype}, state "
        f"{state.shape} {state.dtype})", reference, kernel)


def power_retention_chunk(q, k, v, a, state, norm, counts, reset):
    """A power-retention layer's dispatcher: one chunk of every lane's
    columns against the lane's carried state and normaliser
    (`ops/pallas/retention.power_retention_chunk`, whose docstring has
    the shapes), through the same modes, labeled fallbacks and dispatch
    counters as the paged walks. The kernel walks no block table, so it
    has no generation: it counts under its name,
    "power_retention_chunk", alone. Its reference is the same chunk in
    plain `jax.numpy`."""
    from ..ops.pallas import retention

    def reference():
        return retention.power_retention_chunk_reference(
            q, k, v, a, state, norm, counts, reset)

    def kernel(mode):
        return None, "power_retention_chunk", lambda: \
            retention.power_retention_chunk(q, k, v, a, state, norm,
                                            counts, reset)

    return _dispatch(
        q.ndim == 4 and state.ndim == 4 and norm.ndim == 3
        and state.dtype == norm.dtype == jnp.float32
        and q.dtype in (jnp.float32, jnp.bfloat16)
        and state.shape[2] % 128 == 0,
        f"for power retention (q {q.shape} {q.dtype}, state "
        f"{state.shape} {state.dtype})", reference, kernel)


def _plan_block_writes(block_idx, offset, block_size):
    """Which whole blocks a step's token writes touch (the same few
    small operations for every pool of a step; XLA keeps one copy).

    block_idx, offset: (B, C) int32, the (block, row) each column's
    K/V goes to, masked columns routed to (NULL_BLOCK, 0). The
    scheduler's contract: a lane's live columns are a prefix of its C
    columns and hold consecutive positions (`plan()`:
    `positions[sid, :n] = arange(pos, pos + n)`), so they lie in at
    most J = (C + bs - 2) // bs + 1 blocks: the one column 0 writes,
    and each later one from the column whose row wraps to 0.

    Returns (blocks (B, J) int32 — the blocks lane b writes, NULL
    where it writes fewer; src (B, J, bs) int32 — the column whose
    token lands in that row; hit (B, J, bs) bool — whether any does)."""
    block_idx = jnp.asarray(block_idx, jnp.int32)
    offset = jnp.asarray(offset, jnp.int32)
    c = block_idx.shape[1]
    bs = int(block_size)
    j = jnp.arange((c + bs - 2) // bs + 1, dtype=jnp.int32)
    first = jnp.where(j == 0, 0, j * bs - offset[:, :1])        # (B, J)
    blocks = jnp.where(
        first < c,
        jnp.take_along_axis(block_idx, jnp.minimum(first, c - 1), axis=1),
        NULL_BLOCK)
    rows = jnp.arange(bs, dtype=jnp.int32)
    match = ((block_idx[:, None, None, :] == blocks[:, :, None, None])
             & (offset[:, None, None, :] == rows[None, None, :, None]))
    return blocks, jnp.argmax(match, axis=-1).astype(jnp.int32), \
        match.any(axis=-1)


def write_block_kv(pool, vals, block_idx, offset):
    """Write vals (B, C, H, W) into pool (N, H, bs, W) at
    (block_idx (B, C), :, offset (B, C), :); a scale pool (N, H, bs)
    takes vals (B, C, H) the same way. A step calls it once a layer,
    on `fuse_kv(k, v)` over the fused pool (W = 2*D): one plan, one
    read of the touched blocks, one scatter for K and V both. Masked
    tokens should be routed to (NULL_BLOCK, 0) by the caller, and a
    lane's live columns hold consecutive positions
    (_plan_block_writes). The pool dtype wins (same contract as
    decoding.update_kv_cache).

    The touched blocks are read, overlaid and written back whole, all
    in the row-major layout the Pallas kernels read the pool in, which
    is also how the device keeps a pool whose minor dim fills the 128
    lanes: the step's module holds no copy of a pool (PERF.md section
    6, PR 29; a scatter of single rows, and an XLA gather of blocks
    too, each had XLA:TPU re-lay a 64-lane pool out, PR 26). Lanes
    never share a block they write (copy-on-write comes first), so
    only NULL repeats among the indices, and NULL holds garbage by
    design."""
    from ..ops.pallas.paged import gather_pool_blocks
    blocks, src, hit = _plan_block_writes(block_idx, offset,
                                          pool.shape[2])
    tail = (1,) * (vals.ndim - 2)
    upd = jnp.take_along_axis(vals[:, None],
                              src.reshape(src.shape + tail), axis=2)
    upd = jnp.swapaxes(upd, 2, 3)                   # (B, J, H, bs, ...)
    hit = hit.reshape(hit.shape[:2] + (1, hit.shape[2]) + tail[1:])
    flat = blocks.reshape(-1)
    cur = gather_pool_blocks(pool, flat).reshape(upd.shape)
    new = jnp.where(hit, upd.astype(pool.dtype), cur)
    return pool.at[flat].set(new.reshape((-1,) + new.shape[2:]))


def write_block_kv_quant(pool, k_scale, v_scale, k, v, block_idx,
                         offset):
    """write_block_kv for int8 pools: quantize-at-write. k and v
    (B, C, H, D) float are absmax-quantized per (lane, column, head)
    row, each with a scale of its own; the int8 codes land side by side
    in pool (N, H, bs, 2*D) in ONE write and the f32 scales in
    k_scale / v_scale (N, H, bs) at the same (block, row) address, so a
    block id alone always names all of its data. Returns
    (pool, k_scale, v_scale). Masked tokens route to (NULL_BLOCK, 0)
    like the dense write — the NULL block's codes/scales are garbage by
    design and the kernel/reference never read them."""
    kq, ks = quantize_kv_rows(k)
    vq, vs = quantize_kv_rows(v)
    return (write_block_kv(pool, fuse_kv(kq, vq), block_idx, offset),
            write_block_kv(k_scale, ks, block_idx, offset),
            write_block_kv(v_scale, vs, block_idx, offset))


# ---------------------------------------------------------------------------
# host spill tier
# ---------------------------------------------------------------------------

class HostKVTier:
    """Host-RAM block pool mirroring one PagedKVCache's geometry.

    Same per-layer dict keys as the device pools ("kv" plus
    "k_scale"/"v_scale" for int8) with the same (N, H_kv, bs, 2*D)
    block shape, K beside V, but numpy-backed: eviction under memory pressure becomes a
    device->host copy (``PagedKVCache.spill_block``) that keeps the
    prefix-chain KV alive, and a later hit swaps the block back in
    (``swap_in_block``) instead of re-prefilling. Preempt-and-resume
    scheduling parks a paused request's blocks here too — its host
    blocks ARE its reservation, so the no-mid-flight-OOM invariant
    survives the retirement of full-reservation admission.

    Host block ids are a PRIVATE namespace: they never enter a block
    table and are never attended, so there is no NULL block — all
    `num_blocks` ids are usable (id 0 included). Single-owner free-list
    accounting only (no refcounts: a host block always has exactly one
    owner — a spilled prefix entry or a preempted request's record).
    int8 pools spill as (codes, scales) pairs, so the host tier holds
    ~2x the chains per byte exactly like the device tier (the int8
    compounding noted in docs/serving.md)."""

    def __init__(self, cache, num_blocks):
        if int(num_blocks) < 1:
            raise ValueError("host tier needs >= 1 block")
        self.num_blocks = int(num_blocks)
        self.block_size = cache.block_size
        # the device pools' own block shapes, layer by layer
        shapes = [(self.num_blocks,) + tuple(shp[1:])
                  for shp in cache.layer_shapes]
        # np.dtype() resolves bf16 via the ml_dtypes registration jax
        # itself installs, so the host rows store the device bytes 1:1
        dt = np.dtype(cache.dtype)
        self._itemsize = dt.itemsize
        self._quantized = cache.quantized
        self._elems = sum(int(np.prod(shp)) for shp in shapes)
        self._scale_elems = sum(int(np.prod(shp[:3])) for shp in shapes)
        self.pools = []
        for shape in shapes:
            layer = {"kv": np.zeros(shape, dt)}
            if cache.quantized:
                # scale 1.0 like the device pools: an unwritten row
                # dequantizes to exact zeros without a 0*NaN hazard
                layer["k_scale"] = np.ones(shape[:3], np.float32)
                layer["v_scale"] = np.ones(shape[:3], np.float32)
            self.pools.append(layer)
        # LIFO free list over ALL ids (no NULL reservation) + a used
        # set so a double free fails loudly (the device pool's lesson)
        self._free = list(range(self.num_blocks - 1, -1, -1))
        self._used = set()

    @property
    def num_free(self):
        return len(self._free)

    @property
    def num_used(self):
        return len(self._used)

    def allocate(self, n):
        """n host blocks or None (nothing partial)."""
        if n > len(self._free):
            return None
        taken = [self._free.pop() for _ in range(n)]
        self._used.update(taken)
        return taken

    def free(self, blocks):
        for b in blocks:
            b = int(b)
            if b not in self._used:
                raise ValueError(
                    f"double free of host block {b}: it is already on "
                    f"the free list")
            self._used.discard(b)
            self._free.append(b)

    def pool_bytes(self):
        """Host-RAM bytes of every block pool (k+v across layers,
        including the f32 scale pools when quantized) — the host half
        of the ledger's device/host split."""
        scales = 2 * self._scale_elems * 4 if self._quantized else 0
        return self._elems * self._itemsize + scales


# ---------------------------------------------------------------------------
# pool manager (host side)
# ---------------------------------------------------------------------------

def lane_owned(geometry):
    """What the lanes own in a cache of this `geometry`, for a refusal's
    message: ("window ring", "ROADMAP R3") where a layer is a window
    layer's ring, ("state layer", "ROADMAP R5") where one keeps a state,
    None where every layer's cache is the allocator's blocks."""
    owned = [g for g in geometry or () if isinstance(g, dict)]
    if any("window" in g or "ring_blocks" in g for g in owned):
        return ("window ring", "ROADMAP R3")
    return ("state layer", "ROADMAP R5") if owned else None


def _layer_geometry(g):
    """One layer's `geometry` entry, its numbers as ints: (rows, width)
    of a block, a state layer's `{name: (shape a lane, dtype)}`, or a
    window layer's `{"window": W, "ring_blocks": R}`."""
    if not isinstance(g, dict):
        return (int(g[0]), int(g[1]))
    if "ring_blocks" in g:
        return {"window": int(g["window"]),
                "ring_blocks": int(g["ring_blocks"])}
    return {name: (tuple(int(d) for d in shp), dt)
            for name, (shp, dt) in g.items()}


class PagedKVCache:
    """Device block pools (one fused K|V array per layer, `pools[i]["kv"]`
    of (num_blocks, H_kv, block_size, 2*head_dim)) + a host free list.

    Allocation is host-side bookkeeping only (ints in a list); the
    device arrays are fixed-shape for the process lifetime, so every
    scheduler iteration hits the same compiled step regardless of which
    requests hold which blocks.

    With `mesh=` the pools are laid out head-sharded over the mesh's
    `axis` via NamedSharding — each device holds an
    (num_blocks, H/tp, block_size, 2*D) shard, the Megatron serving
    layout the tp decoders already use for the dense cache. ONLY the
    device layout moves: the free list, the block tables, and every
    allocation decision stay replicated host state, so the scheduler
    above is mesh-agnostic by construction (a block id means the same
    rows on every shard).

    `num_kv_heads` (GQA, ISSUE 16) shrinks the pools' head dim to H_kv
    (H % H_kv == 0; `num_heads` stays the query head count as
    metadata). Every byte number this class reports — pool_bytes,
    scale_bytes, shard_pool_bytes, dense_pool_bytes — is H_kv-true,
    and under a mesh it is H_kv the axis must divide.

    `kv_dtype` selects the POOL storage format on top of `dtype` (the
    compute/activation dtype the dense path would use):

    - None: dense pools in `dtype` (the pre-quantization behavior);
    - "bf16": dense bf16 pools, whatever `dtype` says (a convenience
      alias — identical to dtype=jnp.bfloat16);
    - "int8": int8 pools + per-block-row per-head f32 scale pools
      ("k_scale"/"v_scale" beside "kv" in every layer dict, shape
      (num_blocks, H, block_size), head-sharded the same way). Reads
      dequantize to `dtype`; `pool_bytes()` counts codes AND scales.

    `geometry` (ISSUE 34) gives each layer's block as (rows, width) of
    (rows, block_size, width) where it is not (H_kv, 2 * head_dim): a
    latent layer's (1, W), one row a token. The allocator, the tables,
    the refcounts, COW, the wire and the host tier address whole blocks
    by id and never look inside one, so they are the same code for
    every geometry; every byte count sums the layers' own shapes.

    A STATE layer (ISSUE 36) gives a dict in place of (rows, width):
    `{name: (shape a lane, dtype or None for the serving type)}`, the
    arrays each of the `num_slots` lanes holds for it (a delta-rule
    layer's `{"state": ((H, dv, dk), float32), "conv": ((3, channels),
    None)}`). Its entry of `pools` is those arrays with a leading lane
    axis; `layer_shapes[i]` is None; the block rewriters refuse the
    cache (`_refuse_state`).

    A WINDOW layer (sliding-window attention over W positions) gives
    `{"window": W, "ring_blocks": R}`: its pool is a ring of R blocks a
    lane, (1 + num_slots * R, H_kv, bs, 2 * head_dim) with block 0
    NULL, in which position p of lane s lives in block 1 + s * R +
    (p // bs mod R) (`ring_table`). The allocator never hands out a
    ring block, so admission and `utilization` count the other layers'
    blocks; `layer_shapes[i]` is None, `ring_layers` lists such layers,
    `ring_bytes` counts their pools, and the block rewriters refuse the
    cache as they refuse a state (`_refuse_state`)."""

    def __init__(self, num_layers, num_heads, head_dim, num_blocks,
                 block_size=16, dtype=jnp.float32, mesh=None, axis="tp",
                 kv_dtype=None, num_kv_heads=None, geometry=None,
                 num_slots=None):
        if num_blocks < 2:
            raise ValueError("need >= 2 blocks (block 0 is reserved NULL)")
        if kv_dtype not in (None, "bf16", "int8"):
            raise ValueError(
                f"kv_dtype {kv_dtype!r}: expected None, 'bf16' or "
                f"'int8'")
        self.num_layers = int(num_layers)
        self.num_heads = int(num_heads)
        self.head_dim = int(head_dim)
        self.num_blocks = int(num_blocks)
        self.block_size = int(block_size)
        # GQA: pools physically hold num_kv_heads <= num_heads heads;
        # num_heads stays the QUERY head count (metadata for capacity
        # math and the attention contract above the cache)
        self.num_kv_heads = (int(num_kv_heads) if num_kv_heads
                             else self.num_heads)
        if (self.num_kv_heads < 1
                or self.num_heads % self.num_kv_heads):
            raise ValueError(
                f"num_kv_heads={self.num_kv_heads} must divide "
                f"num_heads={self.num_heads}: grouped-query attention "
                f"maps each group of H/H_kv query heads onto one "
                f"shared KV head, so the group size must be integral")
        self.kv_dtype = kv_dtype
        self.quantized = kv_dtype == "int8"
        # compute_dtype: what a dequantized read yields (and what the
        # dense pools simply store). "bf16" overrides dtype for the
        # dense case so PagedKVCache(kv_dtype="bf16") works standalone.
        self.compute_dtype = (jnp.bfloat16 if kv_dtype == "bf16"
                              else dtype)
        self.dtype = jnp.int8 if self.quantized else self.compute_dtype
        dtype = self.dtype
        self.mesh = mesh
        self.axis = axis if mesh is not None else None
        if mesh is not None and len(mesh.axis_names) != 1:
            # the serving stack shards over exactly ONE (head) axis;
            # data parallelism is separate server replicas, not a mesh
            # axis here — and the per-device ledger rows / shard byte
            # math (pool/tp each) are only truthful on a 1-D mesh
            raise ValueError(
                f"serving mesh must be 1-D (the head axis); got axes "
                f"{mesh.axis_names} — run data-parallel replicas as "
                f"separate GenerationServers instead")
        if mesh is not None and axis not in mesh.axis_names:
            raise ValueError(
                f"axis {axis!r} is not a mesh axis (mesh has "
                f"{mesh.axis_names}) — pass axis=<the mesh's axis name>")
        self.tp = int(mesh.shape[axis]) if mesh is not None else 1
        if self.num_kv_heads % self.tp:
            raise ValueError(
                f"mesh axis {axis!r} size {self.tp} must divide "
                f"num_kv_heads={self.num_kv_heads} (head-sharded "
                f"pools shard the KV heads; with GQA that is H_kv, "
                f"not the {self.num_heads} query heads)")
        # K and V of a token side by side in the minor dim (fuse_kv):
        # at head_dim 64 that is the 128 lanes, and the device keeps
        # the pool row-major, as the kernels read it. `geometry` says
        # otherwise, layer by layer: (rows, width) of a block's
        # (rows, block_size, width), (1, W) for a latent layer
        if geometry is None:
            geometry = [(self.num_kv_heads, 2 * self.head_dim)
                        ] * self.num_layers
        self.geometry = [_layer_geometry(g) for g in geometry]
        if len(self.geometry) != self.num_layers:
            raise ValueError(
                f"geometry names {len(self.geometry)} layers, the cache "
                f"has {self.num_layers}")
        # the layers whose cache a LANE owns: a state a lane, not rows a
        # token, or a window layer's ring of blocks a lane
        self.ring_layers = [i for i, g in enumerate(self.geometry)
                            if isinstance(g, dict) and "ring_blocks" in g]
        self.state_layers = [i for i, g in enumerate(self.geometry)
                             if isinstance(g, dict)
                             and i not in self.ring_layers]
        self.num_slots = int(num_slots) if num_slots else None
        if (self.state_layers or self.ring_layers) and not self.num_slots:
            raise ValueError(
                "a state layer's pool and a window layer's ring have a "
                "lane axis: the cache must be told the lane count "
                "(num_slots=), which the engine owns")
        owned = lane_owned(self.geometry)
        if owned and (self.quantized or mesh is not None):
            raise NotImplementedError(
                f"{'int8 pools' if self.quantized else 'a mesh'} with a "
                f"{owned[0]} (a lane's own cache beside the paged cache): "
                f"it is kept dense and on one device ({owned[1]})")
        self.latent = any(g != (self.num_kv_heads, 2 * self.head_dim)
                          for g in self.geometry if not isinstance(g, dict))
        if self.latent and (self.quantized or mesh is not None):
            raise NotImplementedError(
                "a pool whose block is not (H_kv, bs, 2 * head_dim) "
                "(a latent layer's one row a token) is served dense "
                "and on one device: int8 scales are per K row and per "
                "V row, and the mesh shards the head axis such a pool "
                "does not have (ROADMAP Reach, R4)")
        self.layer_shapes = [
            None if isinstance(g, dict)
            else (self.num_blocks, g[0], self.block_size, g[1])
            for g in self.geometry]
        if mesh is None:
            def make(shp, dt=dtype):
                return jnp.zeros(shp, dt)
        else:
            from jax.sharding import NamedSharding, PartitionSpec as P
            ns = NamedSharding(mesh, P(None, axis, None, None))
            ns3 = NamedSharding(mesh, P(None, axis, None))

            def make(shp, dt=dtype):
                # device= allocates each (N, H/tp, bs, 2*D) shard in
                # place — a zeros-then-device_put would materialize the
                # FULL pool on device 0 first, OOMing at exactly the
                # near-ceiling pool sizes tp serving exists for
                return jnp.zeros(shp, dt,
                                 device=ns if len(shp) == 4 else ns3)

        def make_layer(shape):
            layer = {"kv": make(shape)}
            sshape = shape[:3]      # the (N, H, bs) scale pools
            if self.quantized:
                # scale 1.0, not 0: an unwritten row dequantizes to
                # exact zeros either way, but a zero scale would turn a
                # chaos NaN-poison of the CODES into 0 * NaN = NaN in
                # rows the mask is supposed to neutralize
                layer["k_scale"] = make(sshape, jnp.float32) + 1.0
                layer["v_scale"] = make(sshape, jnp.float32) + 1.0
            return layer

        def make_lane_owned(g):
            if "ring_blocks" in g:
                return {"kv": make(self._ring_shape(g))}
            return {name: jnp.zeros((self.num_slots,) + shp, dt or dtype)
                    for name, (shp, dt) in g.items()}

        self.pools = [make_lane_owned(g) if shp is None
                      else make_layer(shp)
                      for g, shp in zip(self.geometry, self.layer_shapes)]
        # every jitted rewriter of the pools DONATES them (the engine's
        # fused and draft steps, cow_copy, adopt_block_from,
        # deserialize_block, swap_in_block): the old arrays are dead the
        # moment the call returns. So whoever touches `pools` holds this
        # lock from reading the attribute to storing the result, takes
        # the list fresh each time, and keeps no element of it. Sibling
        # caches share the primary's lock (attach_sibling).
        self.pools_lock = threading.RLock()
        # LIFO free list; block 0 (NULL) is never handed out
        self._free = list(range(self.num_blocks - 1, 0, -1))
        # host-side refcounts: block -> live references (absent = free).
        # allocate() hands a block out at refcount 1; the prefix cache
        # and additional requests ref() shared blocks on top.
        self._ref = {}
        # sibling caches whose pools share THIS cache's block ids (the
        # speculative-decoding draft pools): cow_copy copies their rows
        # too, so a repointed table means the same thing in both.
        self._siblings = []
        self._cow_fn = None
        self._xfer_fn = None
        self._wire_in_fn = None
        self.cow_copies = 0
        # host spill tier (enable_host_tier): None until enabled. The
        # two lazy jits are the tier's ENTIRE signature budget — one
        # per direction for the cache lifetime, like _cow_fn/_xfer_fn.
        self.host = None
        self._spill_fn = None
        self._swap_in_fn = None
        self.host_spills = 0
        self.host_swap_ins = 0

    # -- allocation --------------------------------------------------------
    @property
    def usable_blocks(self):
        return self.num_blocks - 1

    # -- byte accounting ---------------------------------------------------
    def pool_bytes(self):
        """LOGICAL bytes of every block pool (k+v across layers,
        INCLUDING the f32 scale pools when quantized) — what the whole
        mesh holds in total, identical to the single-device footprint
        (sharding splits it, never copies). Capacity math keys off this
        number, so quantized pools must report their true int8+scales
        size, never the dense equivalent — and GQA pools their true
        H_kv row count, never the H-head overcount."""
        return (self._pool_elems() * np.dtype(self.dtype).itemsize
                + self.scale_bytes() + self.state_bytes()
                + self.ring_bytes())

    def _pool_elems(self):
        return sum(int(np.prod(shp)) for shp in self.layer_shapes
                   if shp is not None)

    def state_bytes(self):
        """Bytes of the state layers' arrays over all lanes; 0 without
        one."""
        return sum(
            self.num_slots * int(np.prod(shp))
            * np.dtype(dt or self.dtype).itemsize
            for i in self.state_layers
            for shp, dt in self.geometry[i].values())

    def _ring_shape(self, g):
        """A window layer's pool: the NULL block, then R blocks a lane,
        lane after lane (`ring_table`)."""
        return (1 + self.num_slots * g["ring_blocks"], self.num_kv_heads,
                self.block_size, 2 * self.head_dim)

    def ring_bytes(self, dtype=None):
        """Bytes of the window layers' rings over all lanes; 0 without
        one."""
        return sum(int(np.prod(self._ring_shape(self.geometry[i])))
                   * np.dtype(dtype or self.dtype).itemsize
                   for i in self.ring_layers)

    def _refuse_state(self, what):
        """Everything that copies, shares or moves cache BY BLOCK
        cannot carry what a lane owns (`lane_owned`) yet."""
        owned = lane_owned(self.geometry)
        if owned:
            raise NotImplementedError(
                f"{what} with a {owned[0]}: a block id names rows of "
                f"keys and values, and layers "
                f"{sorted(self.state_layers + self.ring_layers)} keep a "
                f"cache a lane that no block id addresses ({owned[1]})")

    def scale_bytes(self):
        """Bytes of the (N, H_kv, bs) f32 scale pools across k+v and
        every layer; 0 for dense pools."""
        if not self.quantized:
            return 0
        return 2 * 4 * sum(int(np.prod(shp[:3]))
                           for shp in self.layer_shapes
                           if shp is not None)

    def dense_pool_bytes(self, dtype=None):
        """What the SAME block count would cost unquantized in `dtype`
        (default: this cache's compute dtype) at this cache's OWN head
        geometry (H_kv for GQA) — the honest denominator for the
        quantization capacity ratio. The GQA saving is a separate
        factor: multiply by num_heads/num_kv_heads for the MHA-dense
        equivalent."""
        dt = dtype if dtype is not None else self.compute_dtype
        return (self._pool_elems() * np.dtype(dt).itemsize
                + self.state_bytes() + self.ring_bytes(dt))

    def shard_pool_bytes(self):
        """Bytes ONE device commits to the pools: pool_bytes()/tp under
        a mesh (the head axis divides exactly), the full pool without
        one. Capacity/watermark math must use THIS number — per-device
        HBM is what admission headroom protects (the HBM ledger's unit,
        compile_insight.array_nbytes_per_device)."""
        return self.pool_bytes() // self.tp

    @property
    def num_free(self):
        return len(self._free)

    @property
    def num_used(self):
        return self.usable_blocks - len(self._free)

    def utilization(self):
        return self.num_used / self.usable_blocks

    def blocks_for_tokens(self, n_tokens):
        return -(-int(n_tokens) // self.block_size)

    def allocate(self, n):
        """n blocks or None (caller backs off; nothing partial)."""
        if n > len(self._free):
            return None
        taken = [self._free.pop() for _ in range(n)]
        for b in taken:
            self._ref[b] = 1
        return taken

    def free(self, blocks):
        """Single-owner release. Refuses a double free (block already
        on the free list) and a free of a block with other live
        references — both were silently accepted before refcounts
        existed, and with cross-request sharing either one hands the
        same block to two requests. Shared blocks go through unref()."""
        for b in blocks:
            b = int(b)
            if b == NULL_BLOCK:
                raise ValueError("freeing the reserved NULL block")
            c = self._ref.get(b, 0)
            if c == 0:
                raise ValueError(
                    f"double free of block {b}: it is already on the "
                    f"free list")
            if c > 1:
                raise ValueError(
                    f"freeing block {b} while {c - 1} other "
                    f"reference(s) are live — shared blocks are "
                    f"released with unref()")
            del self._ref[b]
            self._free.append(b)

    # -- refcounts (cross-request block sharing) ---------------------------
    def ref(self, block):
        """One more reference to an allocated block (a request matching
        a cached prefix chunk, or the prefix index adopting a block)."""
        block = int(block)
        if block == NULL_BLOCK:
            raise ValueError("ref of the reserved NULL block")
        if block not in self._ref:
            raise ValueError(f"ref of free block {block}")
        self._ref[block] += 1

    def unref(self, block):
        """Drop one reference; the block returns to the free list only
        when the LAST reference drops. Returns True when it was freed."""
        block = int(block)
        c = self._ref.get(block, 0)
        if c == 0:
            raise ValueError(f"unref of free block {block}")
        if c == 1:
            del self._ref[block]
            self._free.append(block)
            return True
        self._ref[block] = c - 1
        return False

    def refcount(self, block):
        return self._ref.get(int(block), 0)

    def fork_table(self, blocks):
        """Take one additional reference on every listed block — a
        forked lane's table adopting another lane's live blocks (the
        prompt prefix at group fork, a parent beam's whole table at a
        beam reorder). Pure refcount bookkeeping: no pool bytes move;
        divergence later is the ordinary copy-on-write path. Returns
        the blocks as a fresh list (the caller's private copy to put
        in the new lane's release set)."""
        out = [int(b) for b in blocks]
        for b in out:
            self.ref(b)
        return out

    def unref_blocks(self, blocks):
        """unref() each block — releasing a forked lane, whose table
        mixes private suffix blocks (last ref: freed) with blocks
        sibling lanes or the prefix index still hold (ref drops, block
        lives on). Returns how many were actually freed."""
        freed = 0
        for b in blocks:
            if self.unref(b):
                freed += 1
        return freed

    def is_shared(self, block):
        """True when more than one reference is live (another request
        or the prefix index) — a write must copy-on-write first."""
        return self._ref.get(int(block), 0) >= 2

    # -- copy-on-write -----------------------------------------------------
    def attach_sibling(self, sibling):
        """Register a cache whose pools share this cache's block ids
        (the spec-decode draft pools): cow_copy keeps them consistent."""
        self._siblings.append(sibling)
        sibling.pools_lock = self.pools_lock    # one lock per engine
        self._cow_fn = None         # pytree layout changed: rebuild
        if self.host is not None:
            # host tier already on: the new sibling needs its own host
            # pools at the SAME ids (spill/swap-in move every holder's
            # rows together, draft KV included, so a resumed spec
            # server keeps its warm draft cache)
            self._spill_fn = None
            self._swap_in_fn = None
            sibling.host = HostKVTier(sibling, self.host.num_blocks)

    def cow_copy(self, src, dst):
        """Device-copy block `src`'s rows into block `dst` across every
        layer of this cache's pools AND every sibling's (draft pools
        share block ids, so a repointed table must mean the same rows
        there too). Every array in a layer dict is copied — for a
        quantized cache that includes the k_scale/v_scale pools, so a
        COW-repointed block carries its dequantization state with it
        (mixed fleets work too: each holder copies ITS OWN keys, so a
        dense draft sibling beside a quantized target just copies
        its "kv"). One jitted signature for the cache lifetime: the block
        ids ride as traced scalars, so distinct (src, dst) pairs hit
        the same executable — the fused-step signature budget is
        untouched."""
        self._refuse_state("cow_copy (a forked or shared block)")
        if self._cow_fn is None:
            def _copy(pool_sets, s, d):
                return [
                    [{name: a.at[d].set(a[s]) for name, a in p.items()}
                     for p in pools]
                    for pools in pool_sets]
            self._cow_fn = jax.jit(_copy, donate_argnums=(0,))
        holders = [self] + self._siblings
        with self.pools_lock:
            new_sets = self._cow_fn([h.pools for h in holders],
                                    jnp.asarray(src, jnp.int32),
                                    jnp.asarray(dst, jnp.int32))
            for h, pools in zip(holders, new_sets):
                h.pools = pools
        self.cow_copies += 1

    def adopt_block_from(self, src_cache, src_block, dst_block):
        """Pool-slice transfer BETWEEN caches: copy block `src_block`'s
        rows out of `src_cache`'s pools into this cache's `dst_block`
        across every layer — the disaggregated prefill/decode KV
        handoff primitive (a prefill replica's finished prompt chunks
        move into a decode replica's pool; serving/router.py). The
        cow_copy idiom applied cross-cache: ONE jitted signature per
        cache lifetime (block ids ride as traced scalars), so a
        thousand handoffs compile once and the fused-step signature
        budget is untouched. Geometry (layers/heads/head_dim/
        block_size) must match — replicas of one model always do;
        num_blocks may differ (it is a shape, not an id contract).
        Sibling (draft) pools are NOT transferred: greedy speculative
        decode stays bitwise-correct with a cold draft cache (accept
        rate dips, ids cannot — every committed id is the target's).

        Quantization must MATCH on both sides: a quantized block is an
        (int8 codes, f32 scales) pair, and astype-copying codes into a
        dense pool (or float rows into an int8 pool) would silently
        manufacture garbage KV — exactly the failure this validates
        away. Dense<->dense float dtype differences remain a cast (a
        bf16 prefill tier feeding an f32 decode tier is legitimate);
        quantized<->quantized carries the scale rows alongside the
        codes in the same jitted transfer."""
        self._refuse_state("adopt_block_from (the fleet's KV handoff)")
        src_kv = getattr(src_cache, "num_kv_heads", src_cache.num_heads)
        if (src_cache.num_layers, src_cache.num_heads, src_kv,
                src_cache.head_dim, src_cache.block_size,
                getattr(src_cache, "geometry", self.geometry)) != \
                (self.num_layers, self.num_heads, self.num_kv_heads,
                 self.head_dim, self.block_size, self.geometry):
            raise ValueError(
                f"adopt_block_from needs matching pool geometry; got "
                f"src (L={src_cache.num_layers}, H={src_cache.num_heads},"
                f" H_kv={src_kv}, D={src_cache.head_dim}, "
                f"bs={src_cache.block_size}) vs "
                f"dst (L={self.num_layers}, H={self.num_heads}, "
                f"H_kv={self.num_kv_heads}, D={self.head_dim}, "
                f"bs={self.block_size})")
        if getattr(src_cache, "quantized", False) != self.quantized:
            def _fmt(c):
                return ("int8+scales" if getattr(c, "quantized", False)
                        else f"dense {np.dtype(c.dtype).name}")
            raise ValueError(
                f"adopt_block_from cannot transfer between a quantized "
                f"and a dense pool: src is {_fmt(src_cache)}, dst is "
                f"{_fmt(self)} — int8 codes are meaningless without "
                f"their scale rows and there is no implicit requantize "
                f"path. Build both tiers with the same kv_dtype (the "
                f"fleet handoff contract, docs/serving.md)")
        if self._xfer_fn is None:
            def _xfer(src_pools, dst_pools, s, d):
                return [
                    {name: dp[name].at[d].set(
                        sp[name][s].astype(dp[name].dtype))
                     for name in dp}
                    for sp, dp in zip(src_pools, dst_pools)]
            # the destination is rewritten in place; the source is
            # only read and stays its owner's
            self._xfer_fn = jax.jit(_xfer, donate_argnums=(1,))
        # both caches' locks, in one order whoever calls (two handoffs
        # in opposite directions must not wait on each other)
        first, second = sorted((src_cache.pools_lock, self.pools_lock),
                               key=id)
        with first, second:
            self.pools = self._xfer_fn(
                src_cache.pools, self.pools,
                jnp.asarray(src_block, jnp.int32),
                jnp.asarray(dst_block, jnp.int32))

    # -- wire handoff (out-of-process fleet, serving/transport.py) ---------
    def wire_geometry(self):
        """The block-shape contract a serialized block travels with:
        receivers validate it before touching their pools (the same
        tuple adopt_block_from checks in-process), and `layout` says
        how a block's K and V lie, so that a peer whose pools are laid
        out otherwise is refused, not misread."""
        geo = {"num_layers": self.num_layers,
               "num_heads": self.num_heads,
               "num_kv_heads": self.num_kv_heads,
               "head_dim": self.head_dim,
               "block_size": self.block_size,
               "quantized": bool(self.quantized),
               "layout": KV_LAYOUT}
        if self.latent:
            # a block that is not (H_kv, bs, 2 * head_dim) says what it
            # is, layer by layer: (rows, width)
            geo["block_shapes"] = [list(g) for g in self.geometry]
        return geo

    def serialize_block(self, block):
        """-> (meta, arrays) for block `block`: meta carries the
        wire_geometry + pool-entry names, arrays is one host numpy
        array per (layer, name) — int8 codes next to their f32 scale
        rows when quantized. This is the byte payload of a
        cross-process ``adopt_block_from``; deserialize_block is the
        receiving half."""
        self._refuse_state("serialize_block (the wire)")
        with self.pools_lock:
            names = sorted(self.pools[0].keys())
            # each slice is a device array of its own, queued before
            # whatever step consumes the pools next: the host copies
            # below need no lock
            rows = [layer[name][block]
                    for layer in self.pools for name in names]
        arrays = [np.asarray(r) for r in rows]
        return {"geometry": self.wire_geometry(), "names": names}, arrays

    def deserialize_block(self, dst_block, meta, arrays):
        """Write a serialize_block payload into local block
        `dst_block`, geometry-validated first: a mismatched layout or
        a quantized<->dense mix is rejected with the adopt_block_from
        error contract rather than silently writing garbage KV. One
        jitted write signature per cache lifetime (block id rides as a
        traced scalar)."""
        self._refuse_state("deserialize_block (the wire)")
        g = meta.get("geometry", {})
        if g.get("layout") != KV_LAYOUT:
            raise ValueError(
                f"deserialize_block: the payload's blocks are laid out "
                f"as {g.get('layout', 'separate k and v pools')!r}, "
                f"this cache's as {KV_LAYOUT!r} (one (H_kv, bs, "
                f"2*head_dim) block, K beside V) — the sender runs "
                f"another version of the cache")
        src_geo = (g.get("num_layers"), g.get("num_heads"),
                   g.get("num_kv_heads"), g.get("head_dim"),
                   g.get("block_size"), g.get("block_shapes"))
        if src_geo != (self.num_layers, self.num_heads,
                       self.num_kv_heads, self.head_dim,
                       self.block_size,
                       self.wire_geometry().get("block_shapes")):
            raise ValueError(
                f"deserialize_block needs matching pool geometry; got "
                f"src (L={g.get('num_layers')}, H={g.get('num_heads')}, "
                f"H_kv={g.get('num_kv_heads')}, D={g.get('head_dim')}, "
                f"bs={g.get('block_size')}) vs "
                f"dst (L={self.num_layers}, H={self.num_heads}, "
                f"H_kv={self.num_kv_heads}, D={self.head_dim}, "
                f"bs={self.block_size})")
        if bool(g.get("quantized", False)) != self.quantized:
            src_fmt = ("int8+scales" if g.get("quantized")
                       else "dense float")
            dst_fmt = ("int8+scales" if self.quantized
                       else f"dense {np.dtype(self.dtype).name}")
            raise ValueError(
                f"deserialize_block cannot transfer between a "
                f"quantized and a dense pool: src is {src_fmt}, dst is "
                f"{dst_fmt} — int8 codes are meaningless without their "
                f"scale rows and there is no implicit requantize path. "
                f"Build both tiers with the same kv_dtype (the fleet "
                f"handoff contract, docs/serving.md)")
        names = list(meta.get("names", ()))
        want = sorted(self.pools[0].keys())
        if names != want:
            raise ValueError(
                f"deserialize_block payload names {names} do not match "
                f"this pool's entries {want}")
        expect = self.num_layers * len(names)
        if len(arrays) != expect:
            raise ValueError(
                f"deserialize_block expected {expect} arrays "
                f"({self.num_layers} layers x {len(names)} entries), "
                f"got {len(arrays)} — truncated handoff payload")
        rows = [{name: arrays[li * len(names) + ni]
                 for ni, name in enumerate(names)}
                for li in range(self.num_layers)]
        if self._wire_in_fn is None:
            def _write(pools, rows, d):
                return [
                    {name: layer[name].at[d].set(
                        row[name].astype(layer[name].dtype))
                     for name in layer}
                    for layer, row in zip(pools, rows)]
            self._wire_in_fn = jax.jit(_write, donate_argnums=(0,))
        with self.pools_lock:
            self.pools = self._wire_in_fn(
                self.pools, rows, jnp.asarray(dst_block, jnp.int32))

    # -- host spill tier ---------------------------------------------------
    def enable_host_tier(self, num_blocks):
        """Attach a HostKVTier of `num_blocks` host-RAM blocks to this
        cache (and mirror one onto every sibling at the same ids, so a
        spilled block carries its draft KV with it). Host block ids are
        allocated ONLY from the primary tier's free list — sibling
        tiers are pool storage at mirrored ids, their free lists
        unused. Idempotent resize is NOT supported: one tier per cache
        lifetime, like the pools themselves."""
        self._refuse_state("enable_host_tier (spill, preempt and resume)")
        if self.host is not None:
            raise ValueError(
                "host tier already enabled — it is sized once for the "
                "cache lifetime, like the device pools")
        self.host = HostKVTier(self, num_blocks)
        for sib in self._siblings:
            sib.host = HostKVTier(sib, num_blocks)
        return self.host

    def spill_block(self, block):
        """Device->host copy of block `block`'s rows (every layer,
        every holder — siblings included — scales alongside codes for
        int8). Returns the host block id holding them, or None when
        the host tier is full (caller sheds instead). Does NOT touch
        the device block's refcount/free state: the caller decides
        whether the device copy dies (prefix eviction) or the whole
        request parks (preempt). ONE jitted extract signature for the
        cache lifetime — the block id rides as a traced scalar — and
        one device_get for the whole transfer."""
        self._refuse_state("spill_block (the host tier)")
        if self.host is None:
            raise ValueError("spill_block without enable_host_tier")
        hb = self.host.allocate(1)
        if hb is None:
            return None
        hb = hb[0]
        if self._spill_fn is None:
            def _extract(pool_sets, s):
                return [[{name: a[s] for name, a in p.items()}
                         for p in pools]
                        for pools in pool_sets]
            self._spill_fn = jax.jit(_extract)
        holders = [h for h in [self] + self._siblings
                   if h.host is not None]
        with self.pools_lock:       # reads only: nothing is donated
            rows_sets = self._spill_fn([h.pools for h in holders],
                                       jnp.asarray(block, jnp.int32))
        rows_sets = jax.device_get(rows_sets)
        for h, rows in zip(holders, rows_sets):
            for layer, r in zip(h.host.pools, rows):
                for name, arr in r.items():
                    layer[name][hb] = arr
        self.host_spills += 1
        return hb

    def swap_in_block(self, host_block, dst_block):
        """Host->device copy of host block `host_block`'s rows into
        device block `dst_block` (every layer, every holder) — the
        adopt_block_from idiom pointed at the host pool. The numpy rows
        ride as jit ARGUMENTS (fixed shapes, values not baked), so the
        upload IS the H2D copy and there is ONE swap-in signature for
        the cache lifetime. Does NOT free the host block: the owner
        (prefix entry or preempt record) releases it."""
        self._refuse_state("swap_in_block (the host tier)")
        if self.host is None:
            raise ValueError("swap_in_block without enable_host_tier")
        host_block = int(host_block)
        if self._swap_in_fn is None:
            def _inject(pool_sets, rows_sets, d):
                return [
                    [{name: p[name].at[d].set(
                        rows[name].astype(p[name].dtype))
                      for name in p}
                     for p, rows in zip(pools, rset)]
                    for pools, rset in zip(pool_sets, rows_sets)]
            self._swap_in_fn = jax.jit(_inject, donate_argnums=(0,))
        holders = [h for h in [self] + self._siblings
                   if h.host is not None]
        rows_sets = [
            [{name: arr[host_block] for name, arr in layer.items()}
             for layer in h.host.pools]
            for h in holders]
        with self.pools_lock:
            new_sets = self._swap_in_fn([h.pools for h in holders],
                                        rows_sets,
                                        jnp.asarray(dst_block, jnp.int32))
            for h, pools in zip(holders, new_sets):
                h.pools = pools
        self.host_swap_ins += 1

    def host_pool_bytes(self):
        """Host-RAM bytes of the attached tier(s) — this cache's plus
        every sibling mirror's; 0 with no tier. The host half of the
        ledger's device/host split."""
        if self.host is None:
            return 0
        total = self.host.pool_bytes()
        for sib in self._siblings:
            if sib.host is not None:
                total += sib.host.pool_bytes()
        return total

    # -- layout helpers ----------------------------------------------------
    def make_table(self, blocks, max_blocks):
        """Host block list -> fixed-width int32 row, NULL-padded."""
        t = np.full((max_blocks,), NULL_BLOCK, np.int32)
        t[:len(blocks)] = blocks
        return t


# ---------------------------------------------------------------------------
# dense-interface adapter for decoding.py step_fns
# ---------------------------------------------------------------------------

@jax.tree_util.register_pytree_node_class
class PagedDecodeLayer:
    """One layer's paged cache (its fused pool) behind the dense
    {'k','v'} mapping interface: `layer["k"]` gathers the table's
    blocks and slices the K lanes out into a dense (B, H, M*bs, D) view (positions past t are NULL-block rows, masked
    by the step_fn's own cache_attention_bias), and
    `decoding.update_kv_cache` routes to `paged_update`, which writes
    this step's K/V into the right (block, offset) slot. A pytree, so
    it rides lax.scan carries like the dense dict does.

    Quantized pools compose transparently: with k/v scale pools
    attached, `layer["k"]` dequantizes its gathered view (so the dense
    step_fn math never sees int8) and `paged_update` quantizes at
    write — the existing greedy/sample decode loops run against int8
    KV unchanged."""

    def __init__(self, kv_pool, block_table, k_scale=None,
                 v_scale=None, compute_dtype=None):
        self.kv_pool = kv_pool                  # (N, H, bs, 2*D)
        self.block_table = block_table          # (B, M) int32
        self.k_scale = k_scale                  # (N, H, bs) f32 or None
        self.v_scale = v_scale
        # aux (static, not a leaf): what a dequantized read yields
        self.compute_dtype = compute_dtype

    # pytree protocol -------------------------------------------------------
    def tree_flatten(self):
        return ((self.kv_pool, self.block_table,
                 self.k_scale, self.v_scale), self.compute_dtype)

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        return cls(*leaves, compute_dtype=aux)

    # dense mapping interface ----------------------------------------------
    def __getitem__(self, key):
        if key not in ("k", "v"):
            raise KeyError(key)
        g = gather_block_kv_pair(self.kv_pool,
                                 self.block_table)[key == "v"]
        scale = self.k_scale if key == "k" else self.v_scale
        if scale is None:
            return g
        gs = gather_block_scales(scale, self.block_table)
        cdt = self.compute_dtype or jnp.float32
        return (g.astype(jnp.float32) * gs[..., None]).astype(cdt)

    def paged_update(self, k_t, v_t, t):
        """Write this step's K/V (B, H, 1, D) at logical position t
        (same t for every lane — the lax.scan decode contract). Returns
        a new adapter over the updated pool; the pool dtype wins, same
        as the dense path (int8 pools quantize-at-write)."""
        bs = self.kv_pool.shape[2]
        block_idx = jnp.take_along_axis(
            self.block_table,
            jnp.broadcast_to(t // bs, (self.block_table.shape[0], 1)),
            axis=1)[:, 0]                           # (B,)
        off = t % bs
        if self.k_scale is not None:
            # (B, H, 1, D) -> the (B, C=1, H, D) layout the shared
            # quantized write expects, then index with (B, 1) rows
            bi = block_idx[:, None]
            offs = jnp.broadcast_to(off, bi.shape)
            pool, ks, vs = write_block_kv_quant(
                self.kv_pool, self.k_scale, self.v_scale,
                k_t.transpose(0, 2, 1, 3), v_t.transpose(0, 2, 1, 3),
                bi, offs)
            return PagedDecodeLayer(pool, self.block_table, ks, vs,
                                    compute_dtype=self.compute_dtype)
        pool = self.kv_pool.at[block_idx, :, off, :].set(
            fuse_kv(k_t, v_t)[:, :, 0, :].astype(self.kv_pool.dtype))
        return PagedDecodeLayer(pool, self.block_table,
                                compute_dtype=self.compute_dtype)


def build_paged_decode_cache(cache, batch, max_len):
    """Allocate `batch` rows of `max_len` logical positions out of a
    PagedKVCache and return (cache_pytree, tables, blocks): the pytree
    is a list of PagedDecodeLayer drop-in-compatible with
    decoding.greedy_decode / sample_decode step_fns; `blocks` is the
    flat allocation to hand back to `cache.free` afterwards."""
    m = cache.blocks_for_tokens(max_len)
    rows, flat = [], []
    for _ in range(batch):
        blocks = cache.allocate(m)
        if blocks is None:
            cache.free(flat)
            raise MemoryError(
                f"paged pool exhausted: {batch} x {m} blocks requested, "
                f"{cache.num_free} free")
        rows.append(cache.make_table(blocks, m))
        flat.extend(blocks)
    tables = jnp.asarray(np.stack(rows))
    layers = [PagedDecodeLayer(p["kv"], tables,
                               p.get("k_scale"), p.get("v_scale"),
                               compute_dtype=cache.compute_dtype)
              for p in cache.pools]
    return layers, tables, flat
