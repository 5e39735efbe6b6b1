"""Distribution extensions: a2a expert parallelism, fsdp strategy specs,
loop-aware HLO analyzer."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.analysis import hlo_analyzer as H
from repro.configs import get_config, reduced
from repro.distributed.sharding import Policy, abstract_mesh
from repro.models import moe as M
from repro.models import transformer as T


def _mesh8():
    devs = jax.devices()
    if len(devs) < 8:
        pytest.skip("needs 8 host devices (run under dryrun env)")
    return Mesh(np.array(devs[:8]).reshape(2, 4), ("data", "model"))


# --------------------------------------------------------- a2a MoE

@pytest.fixture(scope="module")
def a2a_setup():
    cfg = dataclasses.replace(reduced(get_config("olmoe-1b-7b")),
                              n_experts=4, experts_per_tok=2,
                              capacity_factor=8.0)
    p = M.moe_init(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, cfg.d_model),
                          jnp.float32)
    return cfg, p, x


def test_a2a_single_device_matches_ref(a2a_setup):
    """On a 1x1 mesh the a2a path degenerates to the dense reference."""
    cfg, p, x = a2a_setup
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    want = M.moe_ref(p, x, cfg)
    with mesh:
        y, aux = jax.jit(lambda p, x: M.moe_forward_a2a(
            p, x, cfg, mesh=mesh, token_axes=("data", "model"),
            expert_axes=("model",), pair_capacity_factor=8.0))(p, x)
    np.testing.assert_allclose(y, want, atol=1e-5)
    assert float(aux) > 0


def test_a2a_grads_flow(a2a_setup):
    cfg, p, x = a2a_setup
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))

    def loss(p):
        y, aux = M.moe_forward_a2a(p, x, cfg, mesh=mesh,
                                   token_axes=("data", "model"),
                                   expert_axes=("model",),
                                   pair_capacity_factor=8.0)
        return jnp.sum(y * y) + aux

    with mesh:
        g = jax.jit(jax.grad(loss))(p)
    assert float(jnp.abs(g["gate"]).sum()) > 0
    assert float(jnp.abs(g["router"]).sum()) > 0


# ----------------------------------------------------- fsdp strategy

def test_fsdp_strategy_drops_tensor_parallel():
    cfg = get_config("mistral-nemo-12b")
    mesh = abstract_mesh((16, 16), ("data", "model"))
    pol = Policy(cfg, mesh, tuned=True, strategy="fsdp")
    aparams = jax.eval_shape(lambda k: T.init_params(cfg, k),
                             jax.random.PRNGKey(0))
    specs = pol.param_pspecs(aparams)
    flat = jax.tree.leaves(specs, is_leaf=lambda s: isinstance(s, P))
    for s in flat:
        axes = [a for a in s if a is not None]
        # weights ZeRO-sharded over both axes together or replicated
        for a in axes:
            assert a == ("data", "model") or a in ("data", "model") and False, s
    assert pol.dp == ("data", "model")


def test_fsdp_strategy_keeps_expert_dim():
    cfg = get_config("deepseek-v3-671b")
    mesh = abstract_mesh((16, 16), ("data", "model"))
    pol = Policy(cfg, mesh, tuned=True, strategy="fsdp")
    assert pol.experts_2d
    aparams = jax.eval_shape(lambda k: T.init_params(cfg, k),
                             jax.random.PRNGKey(0))
    specs = pol.param_pspecs(aparams)
    gate = specs["groups"]["1"]["0"]["mlp"]["gate"]
    assert tuple(gate) == (None, ("data", "model"), None, None)


def test_tuned_head_aware_sharding():
    """kv=8 heads can't shard over model=16: tuned policy replicates."""
    cfg = get_config("mistral-nemo-12b")
    mesh = abstract_mesh((16, 16), ("data", "model"))
    aparams = jax.eval_shape(lambda k: T.init_params(cfg, k),
                             jax.random.PRNGKey(0))
    base = Policy(cfg, mesh).param_pspecs(aparams)
    tuned = Policy(cfg, mesh, tuned=True).param_pspecs(aparams)
    wk_base = base["groups"]["0"]["0"]["mixer"]["wk"]["w"]
    wk_tuned = tuned["groups"]["0"]["0"]["mixer"]["wk"]["w"]
    assert tuple(wk_base)[-1] == "model"       # flat-divisible, head-splitting
    assert tuple(wk_tuned)[-1] is None         # head-aware: replicated


# ------------------------------------------------------ HLO analyzer

HLO_SAMPLE = """
%body (param: (s32[], f32[4,8])) -> (s32[], f32[4,8]) {
  %param = (s32[], f32[4,8]{1,0}) parameter(0)
  %gte0 = s32[] get-tuple-element(%param), index=0
  %gte1 = f32[4,8]{1,0} get-tuple-element(%param), index=1
  %dot = f32[4,8]{1,0} dot(%gte1, %gte1), lhs_contracting_dims={1}, rhs_contracting_dims={1}
  %ag = f32[4,8]{1,0} all-gather(%dot), channel_id=1, dimensions={1}
  ROOT %tuple = (s32[], f32[4,8]{1,0}) tuple(%gte0, %ag)
}

%cond (param.1: (s32[], f32[4,8])) -> pred[] {
  %param.1 = (s32[], f32[4,8]{1,0}) parameter(0)
  %gte = s32[] get-tuple-element(%param.1), index=0
  %c = s32[] constant(7)
  ROOT %lt = pred[] compare(%gte, %c), direction=LT
}

ENTRY %main (p0: f32[4,8]) -> f32[4,8] {
  %p0 = f32[4,8]{1,0} parameter(0)
  %c0 = s32[] constant(0)
  %t = (s32[], f32[4,8]{1,0}) tuple(%c0, %p0)
  %w = (s32[], f32[4,8]{1,0}) while(%t), condition=%cond, body=%body, backend_config={"known_trip_count":{"n":"7"}}
  %ar = f32[4,8]{1,0} all-reduce(%p0), channel_id=2
  ROOT %out = f32[4,8]{1,0} get-tuple-element(%w), index=1
}
"""


def test_analyzer_multiplies_loop_trips():
    t = H.analyze_hlo(HLO_SAMPLE)
    # dot: 2 * 4*8(result) * 8(contracted) = 512 flops x 7 trips
    assert t.flops == pytest.approx(512 * 7)
    ag = 4 * 8 * 4  # f32[4,8] bytes
    assert t.coll["all-gather"] == pytest.approx(ag * 7)
    assert t.coll["all-reduce"] == pytest.approx(ag)
    assert t.coll_count["all-gather"] == 7


def test_analyzer_trip_count_from_condition():
    hlo = HLO_SAMPLE.replace(', backend_config={"known_trip_count":{"n":"7"}}', "")
    t = H.analyze_hlo(hlo)
    assert t.coll_count["all-gather"] == 7   # from constant(7) in %cond


def test_analyzer_on_real_compiled_module():
    def f(w, x):
        def body(c, wi):
            return jnp.tanh(c @ wi), None
        c, _ = jax.lax.scan(body, x, w)
        return c.sum()
    L, B, D = 3, 4, 16
    comp = jax.jit(f).lower(jnp.zeros((L, D, D)), jnp.zeros((B, D))).compile()
    t = H.analyze_hlo(comp.as_text())
    assert t.flops == pytest.approx(L * 2 * B * D * D)


def test_roofline_peaks_by_device_kind():
    """Roofline terms divide by the named chip's published peaks; a kind
    with no entry raises instead of borrowing another chip's peaks."""
    from repro.analysis import roofline as RL
    r = RL.Roofline(flops=197e12, hbm_bytes=819e9, coll_bytes=100e9,
                    device_kind="TPU v5 lite")
    assert (r.t_compute, r.t_memory, r.t_collective) == pytest.approx(
        (1.0, 1.0, 2.0))
    assert r.bottleneck == "collective"
    with pytest.raises(ValueError, match="no published peaks"):
        RL.Roofline(flops=1.0, hbm_bytes=1.0, coll_bytes=1.0,
                    device_kind="cpu").t_compute
