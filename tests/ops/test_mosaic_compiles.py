"""The Pallas kernels COMPILE for a TPU v5e — checked here, without a
chip. libtpu can describe a topology it has no devices for, and jax can
lower and compile against it ahead of time, so a Mosaic refusal (an
unaligned DMA slice, a matmul it cannot type) fails tier-1 instead of
the first chip run. Whether the compiled kernel is RIGHT is
`tests_tpu/`'s question, on the chip.
"""

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from paddle_tpu.ops import attention_ops
from paddle_tpu.ops.pallas import flash, paged

pytestmark = pytest.mark.pallas


@pytest.fixture(scope="module")
def v5e_2x2():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc("v5e:2x2", "tpu")
    except Exception as e:      # noqa: BLE001 — no libtpu, no test
        pytest.skip(f"no compile-only TPU topology here: {e}")
    return topo.devices


@pytest.fixture(scope="module")
def v5e(v5e_2x2):
    return v5e_2x2[0]


def _compile(dev, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=SingleDeviceSharding(dev))
            for s, d in shapes]
    text = jax.jit(fn).trace(*args).lower(
        lowering_platforms=("tpu",)).compile().as_text()
    return [ln for ln in text.splitlines() if "tpu_custom_call" in ln]


def _assert_flash_fwd_bwd_compile(dev, kgrid, monkeypatch, qkv, bias=None,
                                  causal=False):
    """jax.grad of flash attention over q/k/v of `qkv` (shape, dtype),
    with an f32 `bias` shape if given, compiles its three kernels under
    their names."""
    monkeypatch.setattr(flash, "_interpret", lambda: False)
    monkeypatch.setenv("PT_FLASH_KGRID", kgrid)

    def grads(q, k, v, *b):
        return jax.grad(lambda *a: flash.flash_attention(
            *a, bias=b[0] if b else None, causal=causal).astype(
                jnp.float32).sum(), argnums=(0, 1, 2))(q, k, v)

    shapes = [qkv] * 3 + ([(bias, jnp.float32)] if bias else [])
    calls = _compile(dev, grads, *shapes)
    suffix = "_kgrid" if kgrid == "1" else ""
    for name in ("flash_fwd", "flash_dq", "flash_dkv"):
        assert any(f"{name}{suffix})" in c for c in calls), (name, calls)


@pytest.mark.parametrize("kgrid", ["0", "1"], ids=["loop", "kgrid"])
def test_flash_fwd_bwd_compile_at_gpt_geometry(v5e, kgrid, monkeypatch):
    _assert_flash_fwd_bwd_compile(v5e, kgrid, monkeypatch,
                                  ((1, 12, 1024, 64), jnp.float32),
                                  causal=True)


@pytest.mark.parametrize("kgrid", ["0", "1"], ids=["loop", "kgrid"])
def test_flash_bf16_fwd_bwd_compile_at_bert_geometry(v5e, kgrid,
                                                     monkeypatch):
    """The training cell's call: bf16 q/k/v of (16, 16, 512, 64) under
    AMP, a (B, 1, 1, T) f32 padding bias, bf16 tiles into the MXU."""
    _assert_flash_fwd_bwd_compile(v5e, kgrid, monkeypatch,
                                  ((16, 16, 512, 64), jnp.bfloat16),
                                  bias=(16, 1, 1, 512))


@pytest.mark.parametrize("pool", ["bf16", "int8"])
@pytest.mark.parametrize("version", ["v1", "v2"])
def test_paged_kernels_compile_at_smoke_geometry(v5e, version, pool):
    """GPT 12x64 heads, the engine's defaults: 4 slots x 4-token chunks,
    16-token blocks, a 64-block table — head_dim 64 is HALF a lane
    tile, the geometry hand-rolled DMAs could not slice."""
    fn = (paged.ragged_paged_attention if version == "v1"
          else paged.ragged_paged_attention_v2)
    s, h, c, d, bs, m = 4, 12, 4, 64, 16, 64
    n = 1 + s * m
    pdt = jnp.int8 if pool == "int8" else jnp.bfloat16
    shapes = [((s, h, c, d), jnp.bfloat16), ((n, h, bs, 2 * d), pdt),
              ((s, m), jnp.int32), ((s, c), jnp.int32)]
    if pool == "int8":
        shapes += [((n, h, bs), jnp.float32)] * 2
    calls = _compile(
        v5e, lambda *a: fn(*a[:4], *a[4:], interpret=False), *shapes)
    assert len(calls) == 1 and f"paged_attention_{version}" in calls[0]


def test_paged_v1_compiles_at_the_cell_geometry_with_a_grouped_grid(v5e):
    """GPT-2 XL as `gpt2-xl.closed-16` serves it: 16 lanes x 16-token
    chunks, 25 heads x 64, bf16 blocks of 16 tokens, a 64-column table.
    One `paged_attention_v1` call whose grid is one axis of a length
    only the call knows, a step a live group of 8 columns = 128 key
    positions (at most 128 steps, where a column a step took 1,024:
    PERF.md section 6, PR 31), with the eight branches of the value
    path, one a live-group count, all typed by Mosaic."""
    s, h, c, d, bs, m = 16, 25, 16, 64, 16, 64
    shapes = [((s, h, c, d), jnp.bfloat16),
              ((1 + s * m, h, bs, 2 * d), jnp.bfloat16),
              ((s, m), jnp.int32), ((s, c), jnp.int32)]

    def fn(*a):
        return paged.ragged_paged_attention(*a, interpret=False)

    jaxpr = jax.make_jaxpr(fn)(
        *[jax.ShapeDtypeStruct(shp, dt) for shp, dt in shapes])
    # (the call sits in a jit of its own, which the layers share)
    inner, = [e.params["jaxpr"] for e in jaxpr.eqns
              if e.primitive.name in ("pjit", "jit")]
    calls = [e for e in inner.eqns if e.primitive.name == "pallas_call"]
    assert len(calls) == 1
    mapping = calls[0].params["grid_mapping"]
    assert len(mapping.grid) == mapping.num_dynamic_grid_bounds == 1
    # after the bound, the plan: a lane and a group for each of the
    # steps there can be
    lane, group = (v.aval.shape for v in calls[0].invars[1:3])
    assert lane == group == (s * (m * bs // 128),)
    calls = _compile(v5e, fn, *shapes)
    assert len(calls) == 1 and "paged_attention_v1" in calls[0]


@pytest.mark.parametrize("pool,c", [("bf16", 16), ("bf16", 1),
                                    ("int8", 16)],
                         ids=["bf16", "bf16_decode", "int8"])
def test_paged_v2_compiles_at_the_gqa_cell_geometry(v5e, pool, c):
    """Solar-Open2 as `solar-open2-250b-ep16.long8k-closed-16` serves
    it: 16 lanes, 64 query heads on 8 KV heads of 128, bf16 blocks of
    16 tokens, a 512-column table. One `paged_attention_v2` call, a
    step a live group of 8 columns, each KV head's 8 x C query rows one
    tile: the free reshape, the row-to-column mask and the group's
    scratch all typed by Mosaic (and int8 pools past the v1 ceiling)."""
    s, h, hp, d, bs, m = 16, 64, 8, 128, 16, 512
    n = 1 + s * m
    pdt = jnp.int8 if pool == "int8" else jnp.bfloat16
    shapes = [((s, h, c, d), jnp.bfloat16), ((n, hp, bs, 2 * d), pdt),
              ((s, m), jnp.int32), ((s, c), jnp.int32)]
    if pool == "int8":
        shapes += [((n, hp, bs), jnp.float32)] * 2
    calls = _compile(v5e, lambda *a: paged.ragged_paged_attention_v2(
        *a, interpret=False), *shapes)
    assert len(calls) == 1 and "paged_attention_v2" in calls[0]


@pytest.mark.parametrize("c", [16, 1], ids=["prefill", "decode"])
def test_window_walk_compiles_at_the_window_cell_geometry(v5e, c):
    """SmallThinker-21BA3B as `smallthinker-21b-a3b.mixed16k-closed-16`
    serves its window layers: 16 lanes, 28 query heads on 4 KV heads of
    128, bf16 blocks of 16 tokens, a lane's ring of 264 blocks walked
    through a 1,024-column table from the window's first group. One
    v2 call given the window, traced as `paged_window_attention`: the
    windowed plan, the lane's first step that starts the carry, and the
    window's mask, typed by Mosaic."""
    from paddle_tpu.serving import kv_cache as kvc
    s, h, hp, d, bs, m, w = 16, 28, 4, 128, 16, 1024, 4096
    ring = kvc.ring_blocks_for(w, 16, bs)
    assert ring == 264
    shapes = [((s, h, c, d), jnp.bfloat16),
              ((1 + s * ring, hp, bs, 2 * d), jnp.bfloat16),
              ((s, m), jnp.int32), ((s, c), jnp.int32)]
    calls = _compile(v5e, lambda *a: paged.ragged_paged_attention_v2(
        *a, window=w, interpret=False), *shapes)
    assert len(calls) == 1 and "paged_window_attention" in calls[0]


@pytest.mark.parametrize("nested", [False, True],
                         ids=["gspmd", "in_callers_shard_map"])
def test_flash_compiles_under_an_executor_mesh(v5e_2x2, nested,
                                               monkeypatch):
    """`dot_product_attention` under a four-chip `with mesh:`, forward
    and backward. Left to GSPMD the lowering dies ("Mosaic kernels
    cannot be automatically partitioned"), so the op wraps the kernel in
    a shard_map; inside a caller's own full-mesh shard_map (the
    pipeline forward) it must not wrap again. The CPU tests of both
    (tests/parallel/test_flash_on_mesh.py) run the interpreted kernel,
    which is plain XLA — only a TPU lowering sees the difference."""
    import numpy as np

    monkeypatch.setattr(flash, "_interpret", lambda: False)
    monkeypatch.setenv("PADDLE_TPU_FORCE_FLASH", "1")
    mesh = Mesh(np.array(v5e_2x2).reshape(2, 2), ("dp", "pp"))
    spec = P("dp")

    def loss(q):
        o = attention_ops.dot_product_attention(q, q, q, causal=True)
        return o.astype(jnp.float32).sum()

    def fn(q):
        if not nested:
            return jax.grad(loss)(q)
        return jax.shard_map(jax.grad(loss), mesh=mesh, in_specs=spec,
                             out_specs=spec, check_vma=False)(q)

    arg = jax.ShapeDtypeStruct((4, 12, 1024, 64), jnp.float32,
                               sharding=NamedSharding(mesh, spec))
    with mesh:
        text = jax.jit(fn).trace(arg).lower(
            lowering_platforms=("tpu",)).compile().as_text()
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    for name in ("flash_fwd", "flash_dq", "flash_dkv"):
        assert any(name in c for c in calls), (name, calls)


@pytest.mark.parametrize("version", ["v1", "v2"])
@pytest.mark.parametrize("pool", ["bf16", "int8", "bf16_tp4", "bf16_d128"])
def test_kv_write_and_walk_leave_a_fused_pool_where_it_lies(
        v5e_2x2, pool, version):
    """One layer of the fused step at the GPT-2 XL cell's geometry (16
    lanes x 16-token chunks, 25 heads x 64, 1,025 blocks of 16): the
    write and the attention kernel over a donated pool. A TPU kept a
    pool whose minor dim was 64 with the block dim minor, the kernels
    read it row-major, and every step re-laid each pool out, in and
    out (PERF.md section 6, PR 26). The pool is now one array a layer
    with K and V of a token side by side, 128 wide at head_dim 64: the
    device's own layout is the kernels', so the compiled module holds
    NO copy of a whole pool, writes into the buffer it was given, and
    reads the touched blocks and walks the table once a layer, for
    bf16, int8, a tp=4 shard and head_dim 128, under v1 and v2."""
    import re

    import numpy as np

    from paddle_tpu.serving import kv_cache as kvc

    s, h, c, d, bs, m = 16, 25, 16, 64, 16, 64
    if pool == "bf16_d128":
        h, d = 8, 128
    n = 1 + s * m
    pdt = jnp.int8 if pool == "int8" else jnp.bfloat16
    tp = 4 if pool == "bf16_tp4" else 1
    if tp == 1:
        rep = by_head = by_head3 = cols = SingleDeviceSharding(v5e_2x2[0])
    else:
        # the mesh step: pools and q sharded by heads (24 here, for 4
        # chips), the body under shard_map as build_fused_step has it
        h = 24
        mesh = Mesh(np.array(v5e_2x2), ("tp",))
        rep = NamedSharding(mesh, P())
        by_head = NamedSharding(mesh, P(None, "tp", None, None))
        by_head3 = NamedSharding(mesh, P(None, "tp", None))
        cols = NamedSharding(mesh, P(None, None, "tp", None))
    walk = (paged.ragged_paged_attention if version == "v1"
            else paged.ragged_paged_attention_v2)

    def struct(shape, dtype, sharding):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    layer = {"kv": struct((n, h, bs, 2 * d), pdt, by_head)}
    if pool == "int8":
        layer["k_scale"] = struct((n, h, bs), jnp.float32, by_head3)
        layer["v_scale"] = struct((n, h, bs), jnp.float32, by_head3)

    def step(p, q, k, v, tables, pos, bidx, off):
        if pool == "int8":
            kvp, ks, vs = kvc.write_block_kv_quant(
                p["kv"], p["k_scale"], p["v_scale"], k, v, bidx, off)
            new = {"kv": kvp, "k_scale": ks, "v_scale": vs}
        else:
            ks = vs = None
            new = {"kv": kvc.write_block_kv(p["kv"], kvc.fuse_kv(k, v),
                                            bidx, off)}
        return new, walk(q, new["kv"], tables, pos, k_scale=ks,
                         v_scale=vs, interpret=False)

    args = [layer, struct((s, h, c, d), jnp.bfloat16, by_head),
            struct((s, c, h, d), jnp.bfloat16, cols),
            struct((s, c, h, d), jnp.bfloat16, cols),
            struct((s, m), jnp.int32, rep), struct((s, c), jnp.int32, rep),
            struct((s, c), jnp.int32, rep), struct((s, c), jnp.int32, rep)]
    if tp > 1:
        step = jax.shard_map(
            step, mesh=mesh,
            in_specs=({"kv": by_head.spec}, by_head.spec,
                      cols.spec, cols.spec, P(), P(), P(), P()),
            out_specs=({"kv": by_head.spec}, by_head.spec),
            check_vma=False)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(paged, "_interpret", lambda: False)
        text = jax.jit(step, donate_argnums=(0,)).trace(*args).lower(
            lowering_platforms=("tpu",)).compile().as_text()
    header = text[:text.index("\n")]
    # one aliased output per pool leaf: the step writes where it read
    assert len(re.findall(r"(?:may|must)-alias", header)) == len(layer)
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    assert sum("gather_pool_blocks" in ln for ln in calls) == len(layer)
    assert sum(f"paged_attention_{version}" in ln for ln in calls) == 1
    # no copy of a pool. (An int8 layer's two (N, H, bs) f32 scale
    # pools, 3% of its codes, the device keeps with N minor and still
    # re-lays out for each reader: PERF.md section 7.)
    whole_pool = re.compile(
        rf"= \w+\[{n},{h // tp},{bs},{2 * d}\]\S* copy\(")
    assert [ln for ln in text.splitlines() if whole_pool.search(ln)] == []
    # and the pool comes in and goes out row-major, as the kernels read
    # it (an entry layout the device chose, not one this code asked for)
    assert re.search(
        rf"\[{n},{h // tp},{bs},{2 * d}\]\{{3,2,1,0", header), header


def test_latent_walk_compiles_at_the_cells_geometry(v5e):
    """joyai-llm-flash-ep16: 16 lanes x 16-token chunks, 32 heads over
    one 640-wide row a token (512 + 64, padded), 16-token blocks, a
    256-block table: a (512, 640) query tile against 128 keys a step."""
    s, c, h, w, bs, m = 16, 16, 32, 640, 16, 256
    n = 1 + s * m
    calls = _compile(
        v5e, lambda q, pool, tbl, pos: paged.paged_latent_attention(
            q, pool, tbl, pos, value_width=512, scale=192 ** -0.5,
            interpret=False),
        ((s, c, h, w), jnp.bfloat16), ((n, 1, bs, w), jnp.bfloat16),
        ((s, m), jnp.int32), ((s, c), jnp.int32))
    assert len(calls) == 1 and "paged_latent_attention" in calls[0]


@pytest.mark.parametrize("hid,e,activation", [(2048, 16, "silu"),
                                               (2560, 64, "relu")],
                         ids=["joyai_silu", "smallthinker_relu"])
def test_held_experts_kernel_compiles_at_the_cells_geometry(
        v5e, hid, e, activation):
    """The 256 columns of a 16 x 16 step through held experts of
    hid x 768 (gate|up side by side): JoyAI's 16 SiLU-gated of 2048,
    SmallThinker's 64 ReLU-gated of 2560 (each arrives whole)."""
    from paddle_tpu.ops.pallas import moe
    t, inner = 256, 768
    assert moe._inner_blocks(hid, inner, jnp.bfloat16) == 1
    calls = _compile(
        v5e, lambda x, sel, comb, gu, down: moe.moe_experts(
            x, sel, comb, gu, down, interpret=False,
            activation=activation),
        ((t, hid), jnp.bfloat16), ((t, e), jnp.bool_),
        ((t, e), jnp.float32), ((e, hid, 2 * inner), jnp.bfloat16),
        ((e, inner, hid), jnp.bfloat16))
    assert len(calls) == 1 and "moe_experts" in calls[0]


def test_held_experts_kernel_takes_a_wide_expert_in_slices(v5e):
    """solar-open2-250b-ep16: 20 held experts of 4096 x 1280, 31.5 MB
    each: two buffers of a whole expert are 63 MB of VMEM, so the
    kernel takes the inner width in two slices of 640 on a second grid
    axis; at JoyAI's 2048 x 768 (the test above) the expert arrives
    whole, on the one-axis grid it always had."""
    from paddle_tpu.ops.pallas import moe
    assert moe._inner_blocks(2048, 768, jnp.bfloat16) == 1
    assert moe._inner_blocks(4096, 1280, jnp.bfloat16) == 2
    t, hid, inner, e = 256, 4096, 1280, 20
    calls = _compile(
        v5e, lambda x, sel, comb, gu, down: moe.moe_experts(
            x, sel, comb, gu, down, interpret=False),
        ((t, hid), jnp.bfloat16), ((t, e), jnp.bool_),
        ((t, e), jnp.float32), ((e, hid, 2 * inner), jnp.bfloat16),
        ((e, inner, hid), jnp.bfloat16))
    assert len(calls) == 1 and "moe_experts" in calls[0]


def test_chunked_delta_rule_compiles_at_the_cells_geometry(v5e):
    """solar-open2-250b-ep16: 16 lanes x 16-token chunks, 64 heads of
    128 x 128, a float32 state a lane rewritten where it lies."""
    from paddle_tpu.ops.pallas import linear
    s, c, h, d = 16, 16, 64, 128
    bf = jnp.bfloat16
    calls = _compile(
        v5e, lambda q, k, v, g, b, st, cnt, rst: linear.kda_chunk(
            q, k, v, g, b, st, cnt, rst, interpret=False),
        ((s, c, h, d), bf), ((s, c, h, d), bf), ((s, c, h, d), bf),
        ((s, c, h, d), jnp.float32), ((s, c, h), jnp.float32),
        ((s, h, d, d), jnp.float32), ((s,), jnp.int32), ((s,), jnp.bool_))
    assert len(calls) == 1 and "kda_chunk" in calls[0]


def test_power_retention_compiles_at_the_cells_geometry(v5e):
    """brumby-14b-pp5: 16 lanes x 16-token chunks, 40 query heads on 8
    KV heads of 128, a float32 state of 8,320 rows and its normaliser a
    lane, rewritten where they lie, 640 rows a grid step."""
    from paddle_tpu.ops.pallas import retention
    s, c, h, kv, d = 16, 16, 40, 8, 128
    rows = retention.state_rows(d)
    assert rows == 8320 and retention.tile_rows(rows) == 640
    bf = jnp.bfloat16
    calls = _compile(
        v5e, lambda q, k, v, a, st, nm, cnt, rst:
        retention.power_retention_chunk(q, k, v, a, st, nm, cnt, rst,
                                        interpret=False),
        ((s, c, h, d), bf), ((s, c, kv, d), bf), ((s, c, kv, d), bf),
        ((s, c, kv), jnp.float32), ((s, kv, rows, d), jnp.float32),
        ((s, kv, rows), jnp.float32), ((s,), jnp.int32), ((s,), jnp.bool_))
    assert len(calls) == 1 and "power_retention_chunk" in calls[0]


_BRANCH_ATTRS = ("branch_computations", "true_computation",
                 "false_computation")


def _hlo_computations(text):
    """name -> (its instruction lines, {callee: the attribute it is
    called through}) for every computation of an HLO module's text."""
    import re
    comps, name = {}, None
    for ln in text.splitlines():
        head = re.match(r"(?:ENTRY )?%?([\w.\-]+) \(.*\{\s*$", ln)
        if head and not ln.startswith(" "):
            name = "ENTRY" if ln.startswith("ENTRY") else head.group(1)
            comps[name] = ([], {})
        elif name is not None and ln.startswith("}"):
            name = None
        elif name is not None:
            comps[name][0].append(ln)
            for attr, group in re.findall(
                    r"(\w+)=(\{[^}]*\}|%?[\w.\-]+)", ln):
                if attr in ("calls", "to_apply", "body", "condition",
                            *_BRANCH_ATTRS):
                    for callee in re.findall(r"%?([\w.\-]+)", group):
                        comps[name][1][callee] = attr
    return comps


@pytest.mark.parametrize("per_column", [False, True],
                         ids=["last_column", "per_column"])
def test_the_sampling_tails_sorts_sit_in_a_conditional(v5e, per_column):
    """The fused step's tail at the GPT-2 XL cell's geometry (16 lanes
    x 16 columns, 1600 wide, 50,257 ids; the speculative servers' tail
    at 4 columns), compiled for a v5e: both sorts of the whole
    vocabulary are inside a `conditional`'s branch, none is reachable
    from the entry computation without passing through it, so a step
    whose lanes are all greedy does not run them (PERF.md section 6,
    PR 35). A later edit that hoists the draw out of the branch, or a
    compiler pass that does, fails here."""
    from paddle_tpu.serving import step
    s, c, hid, v = 16, (4 if per_column else 16), 1600, 50257

    def tail(x, head, tokens, valid, *ctl):
        return step._step_tail(x, head, tokens, valid, [], per_column,
                               True, *ctl)[1:]

    shapes = (((s, c, hid), jnp.bfloat16), ((hid, v), jnp.bfloat16),
              ((s, c), jnp.int32), ((s, c), jnp.bool_),
              ((s, c, v) if per_column else (s, v), jnp.float32),
              ((s, 2), jnp.uint32), ((s,), jnp.float32), ((s,), jnp.bool_),
              ((s,), jnp.int32), ((s,), jnp.float32))
    args = [jax.ShapeDtypeStruct(sh, d, sharding=SingleDeviceSharding(v5e))
            for sh, d in shapes]
    text = jax.jit(tail).trace(*args).lower(
        lowering_platforms=("tpu",)).compile().as_text()
    comps = _hlo_computations(text)
    assert "ENTRY" in comps

    def reach(roots, through_branches):
        seen, todo = set(), list(roots)
        while todo:
            name = todo.pop()
            if name in seen or name not in comps:
                continue
            seen.add(name)
            todo += [callee for callee, attr in comps[name][1].items()
                     if through_branches or attr not in _BRANCH_ATTRS]
        return seen

    def sorts(names):
        return [ln for n in names for ln in comps[n][0]
                if " sort(" in ln]

    every_step = reach(["ENTRY"], through_branches=False)
    branches = [callee for n in every_step
                for callee, attr in comps[n][1].items()
                if attr in _BRANCH_ATTRS]
    assert len(branches) == 2, branches     # one conditional, two ways
    assert sorts(every_step) == []
    guarded = [sorts(reach([b], through_branches=True)) for b in branches]
    # the sampled branch holds both sorts of f32[16,50257]; the greedy
    # one none
    assert sorted(len(g) for g in guarded) == [0, 2], guarded
    assert all(f"[{s},{v}]" in ln for g in guarded for ln in g)
