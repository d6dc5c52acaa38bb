"""`ServingModel`: what the engine reads of a model is derived from its
spec. Each family at its tiny size gives the facts, the cache geometry
and the names of its step's counts written out below, as each family
stated them by hand before they were derived; a family that runs on
one device refuses a mesh by its name; a spec patched on the class is
what everything derived follows; and the step returns as many counts
as the spec names.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from paddle_tpu.core import framework
from paddle_tpu.core.executor import Executor, Scope, scope_guard
from paddle_tpu.models import brumby, gpt, latent_moe, linear_moe, \
    smallthinker
from paddle_tpu.serving import (BrumbyServingModel, GenerationServer,
                                GPTServingModel, LatentMoEServingModel,
                                LinearMoEServingModel, ServingModel,
                                SmallThinkerServingModel)

pytestmark = pytest.mark.serving

F32 = jnp.float32
MOE = (("moe_assignments", "serving.moe.assignments"),
       ("moe_assignments_held", "serving.moe.assignments_held"),
       ("moe_expert_tokens_max", None), ("moe_experts_touched", None))
KDA = {"state": ((4, 16, 16), F32), "conv": ((3, 192), None)}
WINDOW = {"window": 16}
POWER = {"state": ((2, 256, 16), F32), "norm": ((2, 256), F32)}

# family: (num_layers, num_heads, num_kv_heads, head_dim, max_position,
# kv_geometry, step_counters); GPT's geometry is the pool the cache
# made by default before a model stated one
FACTS = {
    "gpt": (4, 4, 4, 32, 128, [(4, 64)] * 4, ()),
    "latent_moe": (4, 4, 4, 24, 128, [(1, 128)] * 4, MOE),
    "linear_moe": (5, 4, 2, 16, 128, [(2, 32), KDA, KDA, KDA, (2, 32)],
                   MOE + (("kda_lane_calls", None),
                          ("kda_columns", "serving.state.columns"),
                          ("state_resets", "serving.state.resets"))),
    "smallthinker": (8, 4, 2, 16, 128,
                     [(2, 32), WINDOW, WINDOW, WINDOW] * 2,
                     MOE + (("window_lane_calls", None),
                            ("window_key_positions", None))),
    "brumby": (2, 4, 2, 16, 128, [POWER] * 2,
               (("retention_lane_calls", None),
                ("retention_columns", "serving.state.columns"),
                ("state_resets", "serving.state.resets"))),
}

SPEC_FAMILIES = {
    "latent_moe": (LatentMoEServingModel, latent_moe.latent_moe_tiny),
    "linear_moe": (LinearMoEServingModel, linear_moe.linear_moe_tiny),
    "smallthinker": (SmallThinkerServingModel,
                     smallthinker.smallthinker_tiny),
    "brumby": (BrumbyServingModel, brumby.brumby_tiny),
}


def _gpt():
    cfg = gpt.gpt_tiny()
    main, startup = framework.Program(), framework.Program()
    main.random_seed = startup.random_seed = 11
    with framework.program_guard(main, startup):
        gpt.build_lm_net(cfg, seq_len=8)
    scope = Scope()
    with scope_guard(scope):
        Executor().run(startup)
    return GPTServingModel(gpt.load_params(scope, cfg), cfg)


def _model(family):
    if family == "gpt":
        return _gpt()
    cls, tiny = SPEC_FAMILIES[family]
    return cls.from_seed(tiny(), 0)


@pytest.mark.parametrize("family", list(FACTS))
def test_facts_geometry_and_counters_are_the_families_own(family):
    model = _model(family)
    assert isinstance(model, ServingModel)
    layers, heads, kv_heads, head_dim, max_pos, geometry, counters = \
        FACTS[family]
    assert (model.num_layers, model.num_heads, model.num_kv_heads,
            model.head_dim, model.max_position) == (
        layers, heads, kv_heads, head_dim, max_pos)
    assert np.dtype(model.kv_dtype) == np.float32
    assert model.kv_geometry == geometry
    assert model.step_counters == counters


@pytest.mark.parametrize("family", list(SPEC_FAMILIES))
def test_a_one_device_family_refuses_a_mesh_by_name(family):
    model = _model(family)
    mesh = Mesh(np.array(jax.devices()[:1]), ("tp",))
    name = type(model).__name__
    with pytest.raises(NotImplementedError, match=name):
        model.build_fused_step(8, mesh=mesh)
    with pytest.raises(NotImplementedError, match=name):
        model.check_mesh(mesh)


def test_a_spec_patched_on_the_class_is_what_the_model_derives(
        monkeypatch):
    """Nothing derived is copied at construction: a spec replaced on
    the class after the model was made changes its geometry and the
    names of its counts."""
    model = _model("smallthinker")
    spec = SmallThinkerServingModel.step_spec
    monkeypatch.setattr(
        SmallThinkerServingModel, "step_spec",
        lambda self: spec(self)._replace(
            dims={**spec(self).dims, "window": 32}))
    assert model.kv_geometry == [(2, 32), {"window": 32},
                                 {"window": 32}, {"window": 32}] * 2
    monkeypatch.setattr(
        SmallThinkerServingModel, "step_spec",
        lambda self: spec(self)._replace(layers=spec(self).layers[:1]))
    assert model.num_layers == 1
    assert model.step_counters == MOE


@pytest.mark.parametrize("family", list(FACTS))
def test_the_step_returns_as_many_counts_as_the_spec_names(family):
    """The step body appends a count vector by the kinds it meets;
    `step_counters` names it from the spec's kinds: the two agree
    (shapes only, nothing compiled)."""
    model = _model(family)
    srv = GenerationServer(model, num_slots=2, chunk=4, block_size=8,
                           max_context=64, start=False)
    packed = np.zeros((srv._feed.size,), np.int32)
    out = jax.eval_shape(srv._fused, srv.cache.pools, packed,
                         srv._zero_ids, srv._zero_mask)
    names = model.step_counters
    # pools, ids, log-probs, log-prob rows[, counts]
    assert len(out) == 4 + bool(names)
    if names:
        assert out[-1].shape == (len(names),)
    srv.close()
