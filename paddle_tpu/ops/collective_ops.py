"""Collective communication ops.

Parity: paddle/fluid/operators/collective/{c_allreduce,c_broadcast,
c_allgather,c_reducescatter,c_sync_*}_op.* — the NCCL ring collectives.

TPU-first redesign: these lower to XLA collectives (psum/all_gather/
ppermute/psum_scatter) which ride the ICI mesh. They are meaningful inside a
shard_map/pmap context where the named axis exists; when traced outside any
mapped context (single-chip program) they degrade to identity, mirroring how
a 1-GPU NCCL ring is a no-op.
"""

from jax import lax

from . import register


def _axis(ctx):
    return ctx.attr("ring_id_axis", ctx.attr("axis_name", "dp"))


def _maybe(fn, x, axis):
    try:
        return fn(x, axis)
    except NameError:
        return x  # axis not bound: single-device trace


@register("c_allreduce_sum", "c_allreduce", "allreduce")
def c_allreduce_sum(ctx):
    x = ctx.in_("X")
    return {"Out": _maybe(lax.psum, x, _axis(ctx))}


@register("c_allreduce_max")
def c_allreduce_max(ctx):
    return {"Out": _maybe(lax.pmax, ctx.in_("X"), _axis(ctx))}


@register("c_allreduce_min")
def c_allreduce_min(ctx):
    return {"Out": _maybe(lax.pmin, ctx.in_("X"), _axis(ctx))}


@register("c_allreduce_prod")
def c_allreduce_prod(ctx):
    x = ctx.in_("X")

    def pprod(v, ax):
        # no lax.pprod primitive: gather the ring then reduce. An
        # exp(psum(log)) trick would NaN on negatives and -inf on zeros.
        import jax.numpy as jnp
        return jnp.prod(lax.all_gather(v, ax, axis=0), axis=0)
    return {"Out": _maybe(pprod, x, _axis(ctx))}


@register("c_broadcast", "broadcast")
def c_broadcast(ctx):
    x = ctx.in_("X")
    axis = _axis(ctx)
    root = ctx.attr("root", 0)

    def bcast(v, ax):
        idx = lax.axis_index(ax)
        import jax.numpy as jnp
        src = lax.psum(jnp.where(idx == root, v, jnp.zeros_like(v)), ax)
        return src
    return {"Out": _maybe(bcast, x, axis)}


@register("c_allgather")
def c_allgather(ctx):
    x = ctx.in_("X")

    def gather(v, ax):
        return lax.all_gather(v, ax, axis=0, tiled=True)
    return {"Out": _maybe(gather, x, _axis(ctx))}


@register("c_reducescatter")
def c_reducescatter(ctx):
    x = ctx.in_("X")

    def rs(v, ax):
        return lax.psum_scatter(v, ax, scatter_dimension=0, tiled=True)
    return {"Out": _maybe(rs, x, _axis(ctx))}


@register("alltoall")
def alltoall(ctx):
    x = ctx.in_("X")

    def a2a(v, ax):
        return lax.all_to_all(v, ax, split_axis=0, concat_axis=0, tiled=True)
    return {"Out": _maybe(a2a, x, _axis(ctx))}


@register("c_sync_calc_stream", "c_sync_comm_stream")
def c_sync(ctx):
    # XLA schedules compute/comm overlap itself; sync is a no-op by design.
    return {"Out": ctx.in_("X")}


@register("moe")
def moe(ctx):
    """Framework-level Mixture-of-Experts FFN (expert parallelism over
    the mesh 'ep' axis via all_to_all dispatch; dense all-experts-local
    fallback off-mesh). The TPU re-expression of the reference's
    conditional-compute scale story — see parallel/moe.py moe_apply."""
    from ..parallel.moe import moe_apply

    out, aux = moe_apply(
        ctx.in_("X"), ctx.in_("GateW"), ctx.in_("WUp"), ctx.in_("WDown"),
        capacity_factor=ctx.attr("capacity_factor", 1.25))
    return {"Out": out, "AuxLoss": aux.reshape(1)}
