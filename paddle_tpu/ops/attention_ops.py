"""Attention ops.

Parity: the reference composes attention from matmul/softmax primitives
(python/paddle/fluid/layers/nn.py scaled_dot_product_attention and the
book/machine-translation transformer recipe); there is no fused CUDA kernel
in Fluid 1.5. Here attention IS a first-class op so the executor can route
it to a fused Pallas flash-attention kernel on TPU (ops/pallas/flash.py)
— O(T) memory, blockwise softmax in VMEM — with a pure-XLA fallback
everywhere else.
"""

import functools
import os
import warnings

import jax
import jax.numpy as jnp

from . import register

_ring_seg_warned = False


def _use_pallas():
    # PADDLE_TPU_FORCE_FLASH=1 routes attention through the Pallas kernels
    # (interpreter mode off-TPU) — used by tests.
    if os.environ.get("PADDLE_TPU_FORCE_FLASH") == "1":
        return True
    if os.environ.get("PADDLE_TPU_DISABLE_FLASH") == "1":
        return False
    return jax.default_backend() == "tpu"


def _xla_attention(q, k, v, bias=None, scale=None, causal=False):
    """Reference-path attention: (B, H, T, D) q/k/v. XLA fuses the softmax
    chain; fine for CPU tests and a correctness oracle for the Pallas path."""
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    if bias is not None:
        logits = logits + bias
    if causal:
        tq, tk = logits.shape[-2], logits.shape[-1]
        mask = jnp.tril(jnp.ones((tq, tk), jnp.bool_), k=tk - tq)
        logits = jnp.where(mask, logits, jnp.asarray(-1e9, logits.dtype))
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v)


def _active_mesh():
    """The mesh the Executor activated (the `with mesh:` context
    core/compiler._run_data_parallel opens) while its axes are still
    GSPMD's to partition, or None. Inside someone else's shard_map (the
    pipeline forward, parallel/pipeline.py, is a full-mesh one under the
    same `with mesh:`) the axes are already Manual: that caller owns the
    partitioning, the operands here are its local shards, and a second
    shard_map over the same mesh is an error — so there is no mesh left
    for this op to wrap. The legacy mesh context is only readable from a
    private jax module; it is imported without a guard so a jax that
    moves it fails here instead of silently dropping the mesh paths
    below."""
    if jax.sharding.get_abstract_mesh().manual_axes:
        return None
    from jax._src import mesh as mesh_lib
    mesh = mesh_lib.thread_resources.env.physical_mesh
    return None if mesh.empty else mesh


def _active_sp_mesh(q, k, bias):
    """The executor-activated mesh, when sequence parallelism applies:
    mesh has an 'sp' axis > 1, BOTH time axes divide it (cross-attention
    has Tq != Tk), and the bias (if any) is a 4-D key-side bias — the
    shapes ring attention can decompose. Anything else falls back to the
    dense paths, never crashes."""
    if os.environ.get("PADDLE_TPU_DISABLE_RING") == "1":
        return None
    mesh = _active_mesh()
    if mesh is None or "sp" not in mesh.axis_names:
        return None
    sp = mesh.shape["sp"]
    if sp <= 1 or q.shape[2] % sp != 0 or k.shape[2] % sp != 0:
        return None
    if bias is not None and (bias.ndim != 4 or bias.shape[2] != 1
                             or bias.shape[3] != k.shape[2]):
        return None                      # per-query / odd-rank bias
    for name, dim in (("dp", q.shape[0]), ("tp", q.shape[1])):
        if name in mesh.axis_names and dim % mesh.shape[name] != 0:
            return None
    return mesh


def _flash_on_mesh(q, k, v, bias, scale, causal, segment_ids, mesh):
    """The flash kernel under an executor-activated mesh. GSPMD cannot
    partition a Mosaic kernel ("Mosaic kernels cannot be automatically
    partitioned. Please wrap the call in a shard_map"), so the call is
    wrapped here: attention is independent per (batch, head), so batch
    shards over 'dp' and heads over 'tp' (parallel/mesh.make_mesh's axis
    names, as in _active_sp_mesh) where the mesh has those axes and they
    divide; every other axis sees the operands replicated."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    from .pallas.flash import flash_attention

    def ax(name, dim):
        ok = (name in mesh.axis_names and mesh.shape[name] > 1
              and dim % mesh.shape[name] == 0)
        return name if ok else None

    b, h = q.shape[:2]
    b_ax, h_ax = ax("dp", b), ax("tp", h)
    qkv = P(b_ax, h_ax, None, None)
    operands, specs = [q, k, v], [qkv, qkv, qkv]
    if bias is not None:
        bias = bias.reshape((1,) * (4 - bias.ndim) + bias.shape)
        operands.append(bias)
        specs.append(P(b_ax if bias.shape[0] == b else None,
                       h_ax if bias.shape[1] == h else None, None, None))
    seg = segment_ids
    paired = isinstance(seg, (tuple, list))
    if seg is not None:
        for ids in (seg if paired else (seg,)):
            operands.append(ids)
            specs.append(P(b_ax, None))

    def local(q_, k_, v_, *rest):
        rest = list(rest)
        bias_ = rest.pop(0) if bias is not None else None
        seg_ = (tuple(rest) if paired else rest[0]) if rest else None
        return flash_attention(q_, k_, v_, bias=bias_, scale=scale,
                               causal=causal, segment_ids=seg_)

    return shard_map(local, mesh=mesh, in_specs=tuple(specs),
                     out_specs=qkv, check_vma=False)(*operands)


def dot_product_attention(q, k, v, bias=None, scale=None, causal=False,
                          segment_ids=None):
    """Dispatch: ring attention over 'sp' when the Executor activated a
    sequence-parallel mesh (the framework path to long context — K/V and
    the key-side bias rotate over ICI, O(T/sp) memory per chip); else the
    Pallas flash kernel on TPU; else the XLA composition.

    segment_ids (B, T) int enables packed-sequence attention (tokens only
    attend within their own segment). On the flash path the ids are
    compared blockwise inside the kernels (O(T) HBM); the XLA fallback
    materializes the mask (it materializes scores anyway). The ring path
    cannot rotate a per-query mask — packed inputs take the dense paths."""
    if segment_ids is not None and _active_sp_mesh(q, k, bias) is not None:
        global _ring_seg_warned
        if not _ring_seg_warned:
            warnings.warn(
                "packed (segment_ids) attention cannot ride the 'sp' ring "
                "path — the per-query segment mask does not rotate; taking "
                "the dense flash path, so K/V are full-length per chip. "
                "Unpack or drop the sp axis for long-context training.",
                RuntimeWarning, stacklevel=2)
            _ring_seg_warned = True
    sp_mesh = (_active_sp_mesh(q, k, bias)
               if segment_ids is None else None)
    if sp_mesh is not None:
        from ..parallel.ring_attention import ring_attention_sharded
        return ring_attention_sharded(q, k, v, sp_mesh, causal=causal,
                                      scale=scale, bias=bias)
    if _use_pallas():
        # no fallback: a flash kernel that fails to build on TPU must
        # stop the program, not quietly become the O(T^2) XLA path
        mesh = _active_mesh()
        if mesh is not None and mesh.size > 1:
            return _flash_on_mesh(q, k, v, bias, scale, causal,
                                  segment_ids, mesh)
        from .pallas.flash import flash_attention
        return flash_attention(q, k, v, bias=bias, scale=scale,
                               causal=causal, segment_ids=segment_ids)
    if segment_ids is not None:
        from .pallas.flash import segment_mask_bias
        seg_b = (segment_mask_bias(*segment_ids)
                 if isinstance(segment_ids, (tuple, list))
                 else segment_mask_bias(segment_ids))
        bias = seg_b if bias is None else bias + seg_b
    return _xla_attention(q, k, v, bias=bias, scale=scale, causal=causal)


@register("scaled_dot_product_attention", amp_keeps=("Bias",))
def scaled_dot_product_attention_op(ctx):
    """Q/K/V: (B, H, T, D). Optional Bias broadcastable to (B, H, Tq, Tk);
    optional SegmentIds (B, T) for packed-sequence attention."""
    q, k, v = ctx.in_("Q"), ctx.in_("K"), ctx.in_("V")
    bias = ctx.in_("Bias")
    seg = ctx.in_("SegmentIds")
    out = dot_product_attention(
        q, k, v, bias=bias, scale=ctx.attr("scale"),
        causal=bool(ctx.attr("causal", False)), segment_ids=seg)
    return {"Out": out}


@register("multihead_attention",
          amp_keeps=("BQ", "BK", "BV", "BO", "Bias"))
def multihead_attention_op(ctx):
    """Fused projections + attention. Inputs: Query (B, Tq, M),
    Key/Value (B, Tk, M), packed weights WQ/WK/WV (M, M), WO (M, M),
    optional biases and attention Bias. num_heads attr splits M.

    Under AMP the matmul operands (Query/Key/Value, the weights and the
    attention's q/k/v) are bf16; the projection biases and the attention
    Bias are added in f32, as fc adds its bias to `mul`'s product."""
    q_in = ctx.in_("Query")
    k_in = ctx.in_("Key")
    v_in = ctx.in_("Value")
    k_in = q_in if k_in is None else k_in
    v_in = k_in if v_in is None else v_in
    n_heads = ctx.attr("num_heads")
    wq, wk, wv, wo = (ctx.in_("WQ"), ctx.in_("WK"), ctx.in_("WV"),
                      ctx.in_("WO"))
    bq, bk, bv, bo = (ctx.in_("BQ"), ctx.in_("BK"), ctx.in_("BV"),
                      ctx.in_("BO"))
    bias = ctx.in_("Bias")

    def proj(x, w, b):
        y = x @ w
        return y if b is None else y + b

    def split_heads(x):
        b_, t, m = x.shape
        return x.reshape(b_, t, n_heads, m // n_heads).transpose(0, 2, 1, 3)

    # the attention's operands take the weights' type (bf16 under AMP)
    q = split_heads(proj(q_in, wq, bq).astype(wq.dtype))
    k = split_heads(proj(k_in, wk, bk).astype(wk.dtype))
    v = split_heads(proj(v_in, wv, bv).astype(wv.dtype))
    seg = ctx.in_("SegmentIds")
    o = dot_product_attention(q, k, v, bias=bias,
                              causal=bool(ctx.attr("causal", False)),
                              segment_ids=seg)
    b_, h, t, d = o.shape
    o = o.transpose(0, 2, 1, 3).reshape(b_, t, h * d)
    return {"Out": proj(o, wo, bo)}


@register("add_position_encoding")
def add_position_encoding(ctx):
    """Parity: paddle/fluid/operators/add_position_encoding_op.h —
    out = alpha * x + beta * sinusoid(position)."""
    x = ctx.in_("X")  # (B, T, D)
    alpha = ctx.attr("alpha", 1.0)
    beta = ctx.attr("beta", 1.0)
    b, t, d = x.shape
    half = d // 2
    pos = jnp.arange(t, dtype=jnp.float32)[:, None]
    # reference denominator is (half - 1), not half
    # (add_position_encoding_op.h:70: pow(10000, k / (half_size - 1)));
    # half == 1 degenerates to val = position
    denom = float(max(half - 1, 1))
    div = jnp.power(10000.0, jnp.arange(half, dtype=jnp.float32) / denom)
    enc = jnp.concatenate([jnp.sin(pos / div), jnp.cos(pos / div)], axis=-1)
    if enc.shape[-1] < d:  # odd d
        enc = jnp.pad(enc, ((0, 0), (0, d - enc.shape[-1])))
    return {"Out": alpha * x + beta * enc[None].astype(x.dtype)}
