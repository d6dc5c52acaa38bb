"""Mixture-of-Experts with expert parallelism.

Parity target: scale story (reference's pserver sharded embeddings are its
biggest-model mechanism; the TPU equivalent for conditional compute is MoE
over the 'ep' axis with all_to_all dispatch — EP in SURVEY.md §2.6).
What is here is the TRAINING-side sketch: Switch-style TOP-1 softmax gating
with a capacity (a token past its expert's capacity is dropped), an
all_to_all to the experts and back. Nothing under `serving/` imports it.
The served expert layer is `serving/moe.py`: sigmoid scores, top-k with a
selection-only bias, a chip that holds its share of the experts, no
capacity and no dropped token (kernel `ops/pallas/moe.moe_experts`).
"""

import jax
import jax.numpy as jnp
from jax import lax


def top1_gating(logits, capacity):
    """Switch-style top-1 gating. logits: (tokens, experts)."""
    probs = jax.nn.softmax(logits, axis=-1)
    expert = jnp.argmax(probs, axis=-1)
    gate = jnp.max(probs, axis=-1)
    e = logits.shape[-1]
    onehot = jax.nn.one_hot(expert, e)
    pos = jnp.cumsum(onehot, axis=0) * onehot  # rank within expert
    keep = (pos <= capacity).max(axis=-1) > 0
    gate = gate * keep
    # load-balance aux loss (Switch): e * sum(mean_prob * mean_assign)
    aux = e * jnp.sum(jnp.mean(probs, axis=0) * jnp.mean(onehot, axis=0))
    return expert, gate, aux


def expert_parallel_dispatch(x, expert_idx, num_experts, capacity,
                             axis_name="ep"):
    """Scatter tokens to (experts*capacity) slots, all_to_all over ep.
    Call inside shard_map; x: (tokens_local, d)."""
    t, d = x.shape
    onehot = jax.nn.one_hot(expert_idx, num_experts)          # (t, e)
    pos = (jnp.cumsum(onehot, axis=0) * onehot).max(-1) - 1    # rank in expert
    slot = jnp.where(pos < capacity, pos, -1).astype(jnp.int32)
    buf = jnp.zeros((num_experts, capacity, d), x.dtype)
    ok = slot >= 0
    buf = buf.at[expert_idx, jnp.where(ok, slot, 0)].add(
        x * ok[:, None].astype(x.dtype))
    # exchange: each device sends expert-e slab to the device owning e
    out = lax.all_to_all(buf, axis_name, split_axis=0, concat_axis=1,
                         tiled=True)
    return out, (expert_idx, slot, ok)


def expert_parallel_combine(y, dispatch_info, gate, num_experts, capacity,
                            token_count, axis_name="ep"):
    expert_idx, slot, ok = dispatch_info
    back = lax.all_to_all(y, axis_name, split_axis=1, concat_axis=0,
                          tiled=True)
    picked = back[expert_idx, jnp.where(ok, slot, 0)]
    return picked * (gate * ok)[:, None]


def _active_ep_mesh(tokens, num_experts):
    """The executor-activated mesh, when expert parallelism applies:
    an 'ep' axis > 1 that divides both the token count and the expert
    count. Anything else returns None (dense fallback, never crashes).
    Mirrors ops/attention_ops._active_sp_mesh."""
    try:
        from jax._src import mesh as mesh_lib
        mesh = mesh_lib.thread_resources.env.physical_mesh
    except Exception:  # pragma: no cover - jax internals moved
        return None
    if mesh.empty or "ep" not in mesh.axis_names:
        return None
    ep = mesh.shape["ep"]
    if ep <= 1 or tokens % ep != 0 or num_experts % ep != 0:
        return None
    return mesh


def moe_apply(x, gate_w, w_up, w_down, capacity_factor=1.25,
              axis_name="ep"):
    """Framework entry for MoE: dispatch over the Executor-activated
    'ep' mesh axis (all_to_all expert parallelism) or run all experts
    densely when no ep axis is active. x: (..., d); expert weights
    w_up (e, d, f) / w_down (e, f, d); returns (same-shape out, scalar
    load-balance aux loss). This is what the "moe" op lowers to — the
    Program-level path the ops/tests/dryrun drive through exe.run."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    orig_shape = x.shape
    d = orig_shape[-1]
    x2 = x.reshape(-1, d)
    e, _, f = w_up.shape
    layer = MoELayer(d_model=d, d_ff=f, num_experts=e,
                     capacity_factor=capacity_factor, axis_name=axis_name)
    params = {"gate_w": gate_w, "w_up": w_up, "w_down": w_down}
    mesh = _active_ep_mesh(x2.shape[0], e)
    if mesh is None:
        out, aux = layer(params, x2)     # dense: every expert local
    else:
        def local(params, t):
            o, a = layer(params, t)
            return o, a[None]            # scalar -> (1,) so 'ep' shards it

        fn = shard_map(
            local, mesh=mesh,
            in_specs=({"gate_w": P(), "w_up": P(axis_name),
                       "w_down": P(axis_name)}, P(axis_name, None)),
            out_specs=(P(axis_name, None), P(axis_name)), check_vma=False)
        out, aux = fn(params, x2)
        aux = jnp.mean(aux)
    return out.reshape(orig_shape), aux


class MoELayer:
    """Functional MoE FFN block: params is a dict of stacked expert weights
    (local experts on this ep shard)."""

    def __init__(self, d_model, d_ff, num_experts, capacity_factor=1.25,
                 axis_name="ep"):
        self.d_model = d_model
        self.d_ff = d_ff
        self.num_experts = num_experts
        self.capacity_factor = capacity_factor
        self.axis_name = axis_name

    def init_params(self, key):
        k1, k2, k3 = jax.random.split(key, 3)
        e, d, f = self.num_experts, self.d_model, self.d_ff
        s1 = (2.0 / d) ** 0.5
        return {
            "gate_w": jax.random.normal(k3, (d, e)) * 0.02,
            "w_up": jax.random.normal(k1, (e, d, f)) * s1,
            "w_down": jax.random.normal(k2, (e, f, d)) * (2.0 / f) ** 0.5,
        }

    def __call__(self, params, x):
        """x: (tokens_local, d) inside shard_map over 'ep' (or no mesh)."""
        t, d = x.shape
        logits = x @ params["gate_w"]
        capacity = int(self.capacity_factor * t / self.num_experts) + 1
        expert, gate, aux = top1_gating(logits, capacity)
        try:
            dispatched, info = expert_parallel_dispatch(
                x, expert, self.num_experts, capacity, self.axis_name)
            local_e = dispatched.shape[0]
            h = jnp.einsum("ecd,edf->ecf", dispatched,
                           params["w_up"][:local_e])
            h = jax.nn.relu(h)
            y = jnp.einsum("ecf,efd->ecd", h, params["w_down"][:local_e])
            out = expert_parallel_combine(y, info, gate, self.num_experts,
                                          capacity, t, self.axis_name)
        except NameError:
            # no ep axis bound: run all experts locally (dense fallback)
            onehot = jax.nn.one_hot(expert, self.num_experts)
            h = jax.nn.relu(jnp.einsum("td,edf->tef", x, params["w_up"]))
            y = jnp.einsum("tef,efd->ted", h, params["w_down"])
            out = jnp.einsum("ted,te->td", y, onehot) * gate[:, None]
        return out, aux
