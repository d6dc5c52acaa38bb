"""`ServingModel`: the engine's whole view of a model. A family is a
config, its `init_params` and its `step_spec()` (`blocks.StepSpec`);
everything else the engine reads is derived from the spec when it is
asked: the facts (layers, heads, KV heads, head width), the cache's
geometry (`blocks.cache_geometry`), the names of the step's counts
(`blocks.step_counters`) and the fused step itself
(`step.single_device_step`).

    model = SmallThinkerServingModel.from_seed(cfg, seed, jnp.bfloat16)
    srv = GenerationServer(model, num_slots=16, chunk=16,
                           block_size=16, max_context=16384)

`GPTServingModel` adapts models/gpt.py parameters: loaded from a
scope, optionally int8 weights, and the one family whose step also
runs tensor-parallel under a mesh.
"""

import jax
import jax.numpy as jnp

from ..models.gpt import _cast_params, load_params
from ..observability.compile_insight import array_nbytes
from .blocks import LayerSpec, StepSpec, cache_geometry, step_counters
from .step import _fused_step_body, single_device_step

__all__ = ["ServingModel", "GPTServingModel"]


class ServingModel:
    """`params` and `cfg` of one family behind the engine. A subclass
    gives `init_params` (cfg, seed, dtype) -> params, the seeded
    initialiser `from_seed` calls, and `step_spec()`. The cache is kept
    in `dtype`, else in the embedding's type."""

    init_params = None
    int8_weights = 0        # quantized weight tensors (0: dense weights)

    def __init__(self, params, cfg, dtype=None):
        self.params = params
        self.cfg = cfg
        self.kv_dtype = dtype or params["word_emb"].dtype

    @classmethod
    def from_seed(cls, cfg, seed, dtype=jnp.float32):
        """Seeded parameters made on the device in `dtype`."""
        return cls(cls.init_params(cfg, seed, dtype), cfg, dtype=dtype)

    def step_spec(self):
        raise NotImplementedError

    @property
    def num_layers(self):
        return len(self.step_spec().layers)

    @property
    def num_heads(self):
        return self.step_spec().heads

    @property
    def num_kv_heads(self):
        return self.step_spec().kv_heads

    @property
    def head_dim(self):
        # a query head's width; the cache's rows are `kv_geometry`'s
        return self.step_spec().head_dim

    @property
    def max_position(self):
        return self.cfg.max_position

    @property
    def kv_geometry(self):
        return cache_geometry(self.step_spec())

    @property
    def step_counters(self):
        # the step's counts, a last output where the spec has layers
        # that count: each count's name in the iteration record and the
        # registry counter it feeds, or None
        return step_counters(self.step_spec())

    def check_mesh(self, mesh, axis="tp"):
        """Refuses a mesh: a family's step runs on one device unless it
        says how its parameters and pools shard."""
        raise NotImplementedError(
            f"{type(self).__name__} runs on one device: its step takes "
            f"no mesh (ROADMAP D5)")

    def build_fused_step(self, block_size, *, mesh=None, axis="tp",
                         per_column=False, sampling=False,
                         kv_quantized=False):
        """The fused step as `GenerationServer` jits it
        (`step.single_device_step`): `per_column` projects every column
        (speculative verify), `sampling` compiles in the strategies'
        tail; `kv_quantized` says the pools carry int8 scales, which
        only a mesh's pool shardings need."""
        if mesh is not None:
            self.check_mesh(mesh, axis)
        return single_device_step(self.params, self.step_spec(),
                                  block_size, per_column, sampling)

    def param_bytes_per_device(self, mesh=None, axis="tp"):
        """Bytes of the parameter tree one device holds (the HBM
        ledger's per-device unit): without a mesh, the whole tree."""
        return sum(array_nbytes(a)
                   for a in jax.tree_util.tree_leaves(self.params))


class GPTServingModel(ServingModel):
    """models/gpt.py parameters behind the engine's model interface.
    The step math is build_kv_step's, re-expressed over (S, C) ragged
    lanes with paged KV — tests pin the two token-for-token. With a
    mesh the SAME body runs under shard_map: params in the Megatron
    serving layout (gpt.gpt_tp_shardings), pools head-sharded, one psum
    per sub-block (attention o-proj + ffn down-projection). The cache
    is kept in `dtype`, else float32."""

    def __init__(self, params, cfg, dtype=None):
        super().__init__(_cast_params(params, dtype), cfg,
                         dtype or jnp.float32)
        # GQA: cfg.kv_heads < num_heads shares each KV head across a
        # group of query heads; None means MHA (H_kv == H)
        if self.num_heads % self.num_kv_heads:
            raise ValueError(
                f"kv_heads={self.num_kv_heads} must divide "
                f"num_heads={self.num_heads}: grouped-query attention "
                f"needs an integral query-head group per KV head")

    @classmethod
    def from_scope(cls, scope, cfg, dtype=None):
        return cls(load_params(scope, cfg), cfg, dtype=dtype)

    # int8 weight entries a quantize_int8'd layer dict carries in place
    # of each matmul weight (the fused step dequantizes inline)
    INT8_WEIGHT_NAMES = ("wq", "wk", "wv", "wo", "f0w", "f1w")

    def quantize_int8(self):
        """Per-output-channel absmax int8 quantization of every layer's
        matmul weights (the AnalysisConfig.enable_int8 weight side):
        each (in, out) weight w becomes w@q8 int8 codes + w@scale f32
        (1, out) — absmax over the input axis, the reference PTQ
        convention for mul/matmul Y operands (quant/ptq.py). The fused
        step dequantizes inline (codes * scale -> activation dtype), so
        HBM reads halve for these weights and the one-signature-per-
        lifetime budget is untouched. Embeddings, biases and layernorms
        stay float: the word embedding doubles as the lm head (rounding
        it distorts every logit for <2% of the byte win), the rest are
        O(hidden) vectors. Idempotent; returns self."""
        if self.int8_weights:
            return self
        from ..observability import _help
        from ..observability.metrics import global_registry
        # rebind a fresh top-level dict BEFORE rewriting layers: the
        # constructor may hold the caller's own params dict (dtype=None
        # skips the cast-copy), and quantization must never mutate a
        # tree the caller still serves dense elsewhere
        self.params = dict(self.params)
        n = 0
        for i in range(self.cfg.num_layers):
            lp = dict(self.params[f"l{i}"])
            for name in self.INT8_WEIGHT_NAMES:
                wf = lp.pop(name).astype(jnp.float32)
                absmax = jnp.max(jnp.abs(wf), axis=0, keepdims=True)
                scale = jnp.where(absmax > 0, absmax / 127.0, 1.0)
                lp[name + "@q8"] = jnp.clip(
                    jnp.round(wf / scale), -127, 127).astype(jnp.int8)
                lp[name + "@scale"] = scale          # (1, out) f32
                n += 1
            self.params[f"l{i}"] = lp
        self.int8_weights = n
        global_registry().counter(
            "inference.int8.weights",
            _help("inference.int8.weights")).inc(n)
        return self

    def step_spec(self, heads=None, kv_heads=None):
        """This family's block as the fused step reads it
        (`blocks.StepSpec`): LayerNorm with bias, learned positions,
        multi-head attention with biased projections over a K-beside-V
        pool, erf-GELU MLP, head tied to the embedding. `heads` /
        `kv_heads` override the counts for a caller inside a
        shard_map."""
        cfg = self.cfg
        return StepSpec(
            layers=(LayerSpec("layer_norm", "mha", "gelu"),
                    ) * cfg.num_layers,
            positions="learned", tied_head=True,
            heads=heads or cfg.num_heads,
            kv_heads=kv_heads or cfg.kv_heads or cfg.num_heads,
            head_dim=cfg.hidden_size // cfg.num_heads, norm_eps=None,
            dims=None)

    def check_mesh(self, mesh, axis="tp"):
        """The divisibility a tensor-parallel step needs: tp divides the
        query heads, the MLP width and the KV heads."""
        tp = mesh.shape[axis]
        if self.num_heads % tp or self.cfg.inner_size % tp:
            raise ValueError(
                f"tp={tp} must divide both num_heads={self.num_heads} "
                f"and inner_size={self.cfg.inner_size}")
        if self.num_kv_heads % tp:
            raise ValueError(
                f"tp={tp} must divide kv_heads={self.num_kv_heads}: "
                f"the KV pools (and wk/wv columns) shard on the KV "
                f"head axis, so each device needs a whole number of "
                f"KV-head groups")

    def build_fused_step(self, block_size, *, mesh=None, axis="tp",
                         per_column=False, sampling=False,
                         kv_quantized=False):
        params, cfg = self.params, self.cfg

        if mesh is not None and self.int8_weights:
            raise NotImplementedError(
                "int8 weights under a mesh are not supported yet — the "
                "tp shard rules name the dense weight keys; run int8-"
                "weight servers single-device (int8 KV pools DO shard; "
                "docs/serving.md)")
        if mesh is not None and sampling:
            raise NotImplementedError(
                "the sampling/guided step under a mesh is not "
                "supported yet — run fork-group servers single-device "
                "(replicating the mask/rng feeds through shard_map is "
                "follow-up work, docs/serving.md)")
        if mesh is None:
            return super().build_fused_step(
                block_size, per_column=per_column, sampling=sampling)
        if per_column:
            raise NotImplementedError(
                "per-column outputs (speculative verify) are not "
                "supported under a mesh yet")

        from jax import shard_map
        from jax.sharding import PartitionSpec as P
        from ..models.gpt import gpt_tp_shardings

        self.check_mesh(mesh, axis)
        tp = mesh.shape[axis]
        h_loc = self.num_heads // tp
        kv_loc = self.num_kv_heads // tp
        shardings = gpt_tp_shardings(cfg, mesh, axis)
        sharded = jax.device_put(params, shardings)
        # rebind to the sharded copy so THIS model holds no reference
        # to the unsharded source tree — the caller can free theirs and
        # halve the footprint (at the HBM edge that's the difference
        # between fitting and OOM). Shape/dtype consumers
        # (param_bytes*, the ledger) are unaffected; a later
        # single-device build_fused_step on this instance would close
        # over sharded arrays, so use one model per server layout.
        self.params = sharded
        del params

        local_spec = self.step_spec(heads=h_loc, kv_heads=kv_loc)

        def local(lp_all, pools, tokens, positions, valid, tables):
            return _fused_step_body(
                lp_all, local_spec, block_size,
                lambda z: jax.lax.psum(z, axis),
                pools, tokens, positions, valid, tables,
                in_shard_map=True)

        param_specs = jax.tree_util.tree_map(
            lambda ns: ns.spec, shardings)
        layer_spec = {"kv": P(None, axis, None, None)}
        if kv_quantized:
            # the (N, H, bs) scale pools shard on the SAME head axis as
            # their code pools — a shard's rows carry their own scales
            layer_spec["k_scale"] = P(None, axis, None)
            layer_spec["v_scale"] = P(None, axis, None)
        pool_specs = [dict(layer_spec) for _ in range(cfg.num_layers)]
        rep = P()
        fn = shard_map(local, mesh=mesh,
                       in_specs=(param_specs, pool_specs, rep, rep,
                                 rep, rep),
                       out_specs=(pool_specs, rep, rep),
                       check_vma=False)

        def fused(pools, tokens, positions, valid, tables):
            return fn(sharded, pools, tokens, positions, valid, tables)

        return fused

    def param_bytes_per_device(self, mesh=None, axis="tp"):
        """Bytes of the parameter tree ONE device holds under the
        serving layout: sharded leaves (spec mentions `axis`) split by
        tp, replicated leaves count full — the HBM ledger's per-device
        unit. Without a mesh: the whole tree."""
        if mesh is None:
            return super().param_bytes_per_device()
        from ..models.gpt import gpt_tp_shardings
        tp = int(mesh.shape[axis])
        # tree_map over BOTH trees so a params/shardings structure
        # divergence fails loudly instead of zip-truncating silently
        per_leaf = jax.tree_util.tree_map(
            lambda a, ns: array_nbytes(a)
            // (tp if axis in tuple(ns.spec) else 1),
            self.params, gpt_tp_shardings(self.cfg, mesh, axis))
        return sum(jax.tree_util.tree_leaves(per_leaf))
