"""Pipeline parallelism over the pp mesh axis (parallel/pipeline.py).

Capability analog: SURVEY §5.7 "scaling the big thing" — the pp axis was
a name without a feature until round 3 (VERDICT r2 missing #2)."""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from faabric_tpu.models import ModelConfig
from faabric_tpu.models.transformer import init_params, loss_fn
from faabric_tpu.parallel import MeshConfig, build_mesh
from faabric_tpu.parallel.pipeline import (
    bubble_fraction,
    init_pp_train_state,
    make_pp_loss,
    make_pp_train_step,
    microbatch,
    n_ticks,
    pp_data_sharding,
    pp_param_shardings,
    schedule,
    stack_block_params,
    unstack_block_params,
)

CFG = ModelConfig(vocab_size=64, d_model=32, n_layers=4, n_heads=4,
                  d_ff=64, max_seq=32, compute_dtype=jnp.float32)


def data(batch=16, seq=32, seed=0):
    rng = np.random.RandomState(seed)
    return (jnp.asarray(rng.randint(0, 64, (batch, seq)), jnp.int32),
            jnp.asarray(rng.randint(0, 64, (batch, seq)), jnp.int32))


# ---------------------------------------------------------------------------
# Schedule math
# ---------------------------------------------------------------------------

def test_schedule_math():
    assert n_ticks(1, 4) == 4
    assert n_ticks(4, 8) == 11
    assert bubble_fraction(1, 4) == 0.0
    assert bubble_fraction(2, 2) == pytest.approx(1 / 3)

    sched = schedule(3, 4)  # S=3 stages, M=4 microbatches
    assert len(sched) == 6
    # Fill: tick 0 only stage 0 works
    assert sched[0] == [0, None, None]
    # Steady state: diagonal wavefront
    assert sched[2] == [2, 1, 0]
    # Drain: last tick only the last stage works, on the last microbatch
    assert sched[5] == [None, None, 3]
    # Every (stage, microbatch) pair appears exactly once
    seen = {(s, m) for row in sched for s, m in enumerate(row)
            if m is not None}
    assert seen == {(s, m) for s in range(3) for m in range(4)}


def test_microbatch_reshape():
    tokens, _ = data(batch=8)
    mb = microbatch(tokens, 4)
    assert mb.shape == (4, 2, 32)
    np.testing.assert_array_equal(np.asarray(mb).reshape(8, 32),
                                  np.asarray(tokens))
    with pytest.raises(ValueError):
        microbatch(tokens, 3)


def test_stack_unstack_roundtrip():
    params = init_params(jax.random.PRNGKey(0), CFG)
    rt = unstack_block_params(stack_block_params(params))
    assert jax.tree.structure(rt) == jax.tree.structure(params)
    for a, b in zip(jax.tree.leaves(rt), jax.tree.leaves(params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# Numerics vs the dense (pp=1) path
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("pp,tp", [(2, 1), (4, 1), (2, 2)])
def test_pipeline_loss_matches_dense(pp, tp):
    params = init_params(jax.random.PRNGKey(0), CFG)
    tokens, targets = data()
    ref = float(loss_fn(params, tokens, targets, CFG))

    mesh = build_mesh(jax.devices()[:8],
                      MeshConfig(dp=8 // (pp * tp), tp=tp, pp=pp))
    pp_params = jax.device_put(stack_block_params(params),
                               pp_param_shardings(mesh, CFG))
    tok = jax.device_put(microbatch(tokens, 4), pp_data_sharding(mesh))
    tgt = jax.device_put(microbatch(targets, 4), pp_data_sharding(mesh))
    loss = float(jax.jit(make_pp_loss(CFG, mesh))(pp_params, tok, tgt))
    assert abs(loss - ref) < 1e-5


def test_pipeline_gradients_match_dense():
    params = init_params(jax.random.PRNGKey(0), CFG)
    tokens, targets = data(seed=3)

    mesh = build_mesh(jax.devices()[:8], MeshConfig(dp=4, pp=2))
    pp_params = jax.device_put(stack_block_params(params),
                               pp_param_shardings(mesh, CFG))
    tok = jax.device_put(microbatch(tokens, 4), pp_data_sharding(mesh))
    tgt = jax.device_put(microbatch(targets, 4), pp_data_sharding(mesh))

    ploss = make_pp_loss(CFG, mesh)
    g_pp = jax.jit(jax.grad(lambda p: ploss(p, tok, tgt)))(pp_params)
    g_ref = stack_block_params(
        jax.grad(lambda p: loss_fn(p, tokens, targets, CFG))(params))
    assert jax.tree.structure(g_pp) == jax.tree.structure(g_ref)
    for (pa, a), (pb, b) in zip(
            sorted(jax.tree_util.tree_leaves_with_path(g_pp), key=str),
            sorted(jax.tree_util.tree_leaves_with_path(g_ref), key=str)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5,
                                   err_msg=str(pa))


def test_pipeline_train_step_matches_dense():
    """3 optimizer steps on pp=2 track the dense path exactly (adamw is
    elementwise, so stacked vs per-layer trees update identically)."""
    from faabric_tpu.models import (
        data_sharding,
        init_train_state,
        make_optimizer,
        make_train_step,
    )

    tokens, targets = data(seed=5)

    # Dense path
    mesh_d = build_mesh(jax.devices()[:8], MeshConfig(dp=8))
    opt = make_optimizer()
    params, opt_state = init_train_state(jax.random.PRNGKey(1), CFG,
                                         mesh_d, opt)
    step_d = make_train_step(CFG, mesh_d, opt)
    t_d = jax.device_put(tokens, data_sharding(mesh_d))
    y_d = jax.device_put(targets, data_sharding(mesh_d))
    dense_losses = []
    for _ in range(3):
        params, opt_state, loss = step_d(params, opt_state, t_d, y_d)
        dense_losses.append(float(loss))

    # Pipeline path, same init seed
    mesh_p = build_mesh(jax.devices()[:8], MeshConfig(dp=4, pp=2))
    opt_p = make_optimizer()
    pp_params, pp_opt = init_pp_train_state(jax.random.PRNGKey(1), CFG,
                                            mesh_p, opt_p)
    step_p = make_pp_train_step(CFG, mesh_p, opt_p, n_microbatches=4)
    pp_losses = []
    for _ in range(3):
        pp_params, pp_opt, loss = step_p(pp_params, pp_opt, tokens, targets)
        pp_losses.append(float(loss))

    assert all(np.isfinite(x) for x in pp_losses)
    np.testing.assert_allclose(pp_losses, dense_losses, rtol=1e-5)


def test_pipeline_rejects_bad_configs():
    mesh = build_mesh(jax.devices()[:8], MeshConfig(dp=4, pp=2))
    with pytest.raises(ValueError, match="not divisible"):
        make_pp_loss(ModelConfig(vocab_size=64, d_model=32, n_layers=3,
                                 n_heads=4, d_ff=64, max_seq=32), mesh)
    # ep>1 on a DENSE config is rejected (experts are a MoE concept)
    mesh_ep = build_mesh(jax.devices()[:8], MeshConfig(dp=2, ep=2, pp=2))
    with pytest.raises(ValueError, match="MoE config"):
        make_pp_loss(CFG, mesh_ep)


@pytest.mark.parametrize("kind", [dict(ffn="swiglu"),
                                  dict(norm_placement="sandwich"),
                                  dict(rope_pairing="halves"),
                                  dict(norm_eps=1e-5),
                                  dict(n_passes=2)],
                         ids=lambda kind: next(iter(kind)))
@pytest.mark.parametrize("moe", [False, True], ids=["dense", "moe"])
def test_pipeline_refuses_block_kinds_it_lacks(kind, moe):
    """The stages' own blocks are GELU, pre-norm, neighbours, eps 1e-6,
    one pass: a configuration that names another kind is refused by
    name, not trained as another network."""
    cfg = dataclasses.replace(_moe_cfg() if moe else CFG, **kind)
    shape = MeshConfig(dp=2, pp=2, ep=2) if moe else MeshConfig(dp=4, pp=2)
    mesh = build_mesh(jax.devices()[:8], shape)
    (field,) = kind
    for schedule_name in ("gpipe", "1f1b"):
        with pytest.raises(ValueError, match=field):
            make_pp_train_step(cfg, mesh, schedule_name=schedule_name)


def test_pipeline_deep_config_pp4_tp2():
    """8 layers over pp=4 stages with tp=2 (dp=1): the deepest topology
    an 8-device mesh carries; loss matches dense."""
    cfg = ModelConfig(vocab_size=64, d_model=32, n_layers=8, n_heads=4,
                      d_ff=64, max_seq=32, compute_dtype=jnp.float32)
    params = init_params(jax.random.PRNGKey(4), cfg)
    tokens, targets = data(batch=4, seed=9)
    ref = float(loss_fn(params, tokens, targets, cfg))

    mesh = build_mesh(jax.devices()[:8], MeshConfig(dp=1, tp=2, pp=4))
    pp_params = jax.device_put(stack_block_params(params),
                               pp_param_shardings(mesh, cfg))
    tok = jax.device_put(microbatch(tokens, 4), pp_data_sharding(mesh))
    tgt = jax.device_put(microbatch(targets, 4), pp_data_sharding(mesh))
    loss = float(jax.jit(make_pp_loss(cfg, mesh))(pp_params, tok, tgt))
    assert abs(loss - ref) < 1e-5


def test_pipeline_checkpoint_interop(tmp_path):
    """pp params round-trip through the standard checkpoint path via
    unstack/stack — one checkpoint format serves both layouts."""
    from faabric_tpu.models import make_optimizer
    from faabric_tpu.models.checkpoint import (
        restore_train_state,
        save_train_state,
    )

    mesh = build_mesh(jax.devices()[:8], MeshConfig(dp=4, pp=2))
    opt = make_optimizer()
    pp_params, pp_opt = init_pp_train_state(jax.random.PRNGKey(6), CFG,
                                            mesh, opt)
    step = make_pp_train_step(CFG, mesh, opt, n_microbatches=4)
    tokens, targets = data(seed=7)
    pp_params, pp_opt, loss0 = step(pp_params, pp_opt, tokens, targets)

    # Save in the DENSE layout (the interchange format)
    dense = unstack_block_params(jax.device_get(pp_params))
    save_train_state(str(tmp_path / "ck"), dense, None, step=1)
    r_dense, _, st = restore_train_state(str(tmp_path / "ck"))
    assert st == 1

    restored = jax.device_put(stack_block_params(r_dense),
                              pp_param_shardings(mesh, CFG))
    # Same params → same next loss on the same data
    opt2 = make_optimizer()
    step2 = make_pp_train_step(CFG, mesh, opt2, n_microbatches=4)
    _, _, loss_a = step(pp_params, pp_opt, tokens, targets)
    _, _, loss_b = step2(restored, opt2.init(restored), tokens, targets)
    # Optimizer states differ (fresh vs stepped), but the LOSS is a pure
    # function of params+data and must match
    np.testing.assert_allclose(float(loss_a), float(loss_b), rtol=1e-6)


# ---------------------------------------------------------------------------
# 1F1B schedule (hand-scheduled interleaved fwd/bwd, O(S) activations)
# ---------------------------------------------------------------------------

def test_1f1b_schedule_math():
    from faabric_tpu.parallel.pipeline import n_ticks_1f1b, ring_slots

    assert n_ticks_1f1b(1, 4) == 4
    assert n_ticks_1f1b(4, 8) == 14
    assert ring_slots(1) == 1
    assert ring_slots(4) == 7
    # Ring slots bound in-flight microbatches for every stage: the fwd/
    # bwd index distance is 2(S-1) - 2s <= 2(S-1) < ring_slots(S)
    for S in (2, 3, 4):
        for s in range(S):
            assert 2 * (S - 1) - 2 * s < ring_slots(S)


@pytest.mark.parametrize("pp,tp,m", [(2, 1, 4), (4, 1, 8), (2, 2, 4)])
def test_1f1b_loss_and_grads_match_autodiff_gpipe(pp, tp, m):
    from faabric_tpu.parallel.pipeline import make_pp_1f1b_value_and_grad

    params = init_params(jax.random.PRNGKey(0), CFG)
    tokens, targets = data(seed=5)

    mesh = build_mesh(jax.devices()[:8],
                      MeshConfig(dp=8 // (pp * tp), tp=tp, pp=pp))
    pp_params = jax.device_put(stack_block_params(params),
                               pp_param_shardings(mesh, CFG))
    tok = jax.device_put(microbatch(tokens, m), pp_data_sharding(mesh))
    tgt = jax.device_put(microbatch(targets, m), pp_data_sharding(mesh))

    loss_1f1b, g_1f1b = jax.jit(make_pp_1f1b_value_and_grad(CFG, mesh))(
        pp_params, tok, tgt)

    ploss = make_pp_loss(CFG, mesh)
    loss_ref, g_ref = jax.jit(jax.value_and_grad(
        lambda p: ploss(p, tok, tgt)))(pp_params)

    assert abs(float(loss_1f1b) - float(loss_ref)) < 1e-5
    assert jax.tree.structure(g_1f1b) == jax.tree.structure(g_ref)
    for (pa, a), (pb, b) in zip(
            sorted(jax.tree_util.tree_leaves_with_path(g_1f1b), key=str),
            sorted(jax.tree_util.tree_leaves_with_path(g_ref), key=str)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=3e-5,
                                   err_msg=str(pa))


def test_1f1b_train_step_matches_gpipe_schedule():
    from faabric_tpu.parallel.pipeline import (
        init_pp_train_state,
        make_pp_train_step,
    )

    tokens, targets = data(seed=9)
    mesh = build_mesh(jax.devices()[:8], MeshConfig(dp=4, pp=2))

    losses = {}
    for sched_name in ("gpipe", "1f1b"):
        pp_params, opt_state = init_pp_train_state(
            jax.random.PRNGKey(1), CFG, mesh)
        step = make_pp_train_step(CFG, mesh, n_microbatches=4,
                                  schedule_name=sched_name)
        ls = []
        for _ in range(3):
            pp_params, opt_state, loss = step(pp_params, opt_state,
                                              tokens, targets)
            ls.append(float(loss))
        losses[sched_name] = ls
    np.testing.assert_allclose(losses["1f1b"], losses["gpipe"], atol=2e-5)
    assert losses["1f1b"][-1] < losses["1f1b"][0]  # it actually learns


# ---------------------------------------------------------------------------
# MoE stages: pp × ep (× tp) composed in one program
# ---------------------------------------------------------------------------

def _moe_cfg():
    from faabric_tpu.models.moe import MoEConfig

    # aux_loss_weight=0: the pipeline path does not compute the switch
    # aux loss (head-anchored schedules carry one scalar), so parity is
    # checked against the global MoE path with aux excluded
    return MoEConfig(vocab_size=32, d_model=16, n_layers=2, n_heads=2,
                     d_ff=32, max_seq=16, compute_dtype=jnp.float32,
                     n_experts=4, aux_loss_weight=0.0, remat=False)


def _moe_data(cfg, batch=4, seed=3):
    rng = np.random.RandomState(seed)
    return (jnp.asarray(rng.randint(0, cfg.vocab_size, (batch, cfg.max_seq)),
                        jnp.int32),
            jnp.asarray(rng.randint(0, cfg.vocab_size, (batch, cfg.max_seq)),
                        jnp.int32))


@pytest.mark.parametrize("shape", [dict(dp=2, pp=2, ep=2),
                                   dict(pp=2, ep=2, tp=2)])
def test_pipeline_moe_loss_matches_global(shape):
    """Switch-MoE stages inside the pipeline: expert slabs over ep,
    expert hidden over tp, layers over pp — loss must equal the
    single-mesh MoE forward exactly (same fp32 routing math)."""
    from faabric_tpu.models.moe import init_moe_params, moe_loss_fn
    from faabric_tpu.parallel.pipeline import make_pp_loss

    cfg = _moe_cfg()
    params = init_moe_params(jax.random.PRNGKey(0), cfg)
    tokens, targets = _moe_data(cfg)
    ref = float(moe_loss_fn(params, tokens, targets, cfg))

    mesh = build_mesh(jax.devices()[:8], MeshConfig(**shape))
    pp_params = jax.device_put(stack_block_params(params),
                               pp_param_shardings(mesh, cfg))
    tok = jax.device_put(microbatch(tokens, 2), pp_data_sharding(mesh))
    tgt = jax.device_put(microbatch(targets, 2), pp_data_sharding(mesh))
    loss = float(jax.jit(make_pp_loss(cfg, mesh))(pp_params, tok, tgt))
    assert abs(loss - ref) < 1e-5, (loss, ref)


def test_pipeline_moe_train_step_schedules_agree():
    """GPipe-by-grad and hand-scheduled 1F1B must produce identical
    losses through MoE stages (the 1F1B vjp differentiates the routing
    + ep-local expert compute + psums)."""
    from faabric_tpu.parallel.pipeline import (
        init_pp_train_state,
        make_pp_train_step,
    )

    cfg = _moe_cfg()
    tokens, targets = _moe_data(cfg, seed=11)
    mesh = build_mesh(jax.devices()[:8], MeshConfig(dp=2, pp=2, ep=2))

    losses = {}
    for sched_name in ("gpipe", "1f1b"):
        pp_params, opt_state = init_pp_train_state(
            jax.random.PRNGKey(1), cfg, mesh)
        step = make_pp_train_step(cfg, mesh, n_microbatches=2,
                                  schedule_name=sched_name)
        ls = []
        for _ in range(3):
            pp_params, opt_state, loss = step(pp_params, opt_state,
                                              tokens, targets)
            ls.append(float(loss))
        losses[sched_name] = ls
    np.testing.assert_allclose(losses["1f1b"], losses["gpipe"], atol=2e-5)
    assert losses["1f1b"][-1] < losses["1f1b"][0]  # it actually learns


def test_pipeline_moe_rejects_bad_ep():
    from faabric_tpu.parallel.pipeline import make_pp_loss

    cfg = _moe_cfg()  # 4 experts
    cfg = dataclasses_replace_experts(cfg, 6)
    mesh = build_mesh(jax.devices()[:8], MeshConfig(pp=2, ep=4))
    with pytest.raises(ValueError, match="divisible by ep"):
        make_pp_loss(cfg, mesh)


def dataclasses_replace_experts(cfg, n):
    import dataclasses

    return dataclasses.replace(cfg, n_experts=n)


# ---------------------------------------------------------------------------
# Sequence parallelism inside pipeline stages: sp × pp (× dp × tp)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape", [dict(dp=2, sp=2, pp=2),
                                   dict(sp=2, pp=2, tp=2)])
def test_pipeline_sp_loss_matches_dense(shape):
    """Sequence-sharded pipeline stages (activations/Q over sp, K/V
    gathered with the causal row-offset mask) must reproduce the dense
    loss."""
    from faabric_tpu.parallel.pipeline import make_pp_loss

    params = init_params(jax.random.PRNGKey(0), CFG)
    tokens, targets = data()
    ref = float(loss_fn(params, tokens, targets, CFG))

    mesh = build_mesh(jax.devices()[:8], MeshConfig(**shape))
    pp_params = jax.device_put(stack_block_params(params),
                               pp_param_shardings(mesh, CFG))
    tok = jax.device_put(microbatch(tokens, 4), pp_data_sharding(mesh))
    tgt = jax.device_put(microbatch(targets, 4), pp_data_sharding(mesh))
    loss = float(jax.jit(make_pp_loss(CFG, mesh))(pp_params, tok, tgt))
    assert abs(loss - ref) < 1e-5, (loss, ref)


def test_pipeline_sp_1f1b_gradients_match_dense():
    """The hand-scheduled 1F1B backward through sequence-sharded stages
    (gathered-KV attention vjp + sp-invariant cotangent psums + the
    embed-grad psum over row-disjoint sp shards) must match jax.grad of
    the dense loss."""
    from faabric_tpu.parallel.pipeline import make_pp_1f1b_value_and_grad

    params = init_params(jax.random.PRNGKey(0), CFG)
    tokens, targets = data()
    g_ref = jax.grad(loss_fn)(params, tokens, targets, CFG)

    mesh = build_mesh(jax.devices()[:8], MeshConfig(dp=2, sp=2, pp=2))
    pp_params = jax.device_put(stack_block_params(params),
                               pp_param_shardings(mesh, CFG))
    tok = jax.device_put(microbatch(tokens, 4), pp_data_sharding(mesh))
    tgt = jax.device_put(microbatch(targets, 4), pp_data_sharding(mesh))
    _, grads = jax.jit(make_pp_1f1b_value_and_grad(CFG, mesh))(
        pp_params, tok, tgt)
    g_pp = unstack_block_params(jax.tree.map(np.asarray, grads))
    for (pa, a), (pb, b) in zip(
            sorted(jax.tree_util.tree_leaves_with_path(g_pp), key=str),
            sorted(jax.tree_util.tree_leaves_with_path(g_ref), key=str)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=3e-5,
                                   err_msg=str(pa))


def test_pipeline_sp_train_step_schedules_agree():
    from faabric_tpu.parallel.pipeline import (
        init_pp_train_state,
        make_pp_train_step,
    )

    tokens, targets = data(seed=13)
    mesh = build_mesh(jax.devices()[:8], MeshConfig(dp=2, sp=2, pp=2))
    losses = {}
    for sched_name in ("gpipe", "1f1b"):
        pp_params, opt_state = init_pp_train_state(
            jax.random.PRNGKey(1), CFG, mesh)
        step = make_pp_train_step(CFG, mesh, n_microbatches=4,
                                  schedule_name=sched_name)
        ls = []
        for _ in range(3):
            pp_params, opt_state, loss = step(pp_params, opt_state,
                                              tokens, targets)
            ls.append(float(loss))
        losses[sched_name] = ls
    np.testing.assert_allclose(losses["1f1b"], losses["gpipe"], atol=2e-5)
    assert losses["1f1b"][-1] < losses["1f1b"][0]


def test_pipeline_moe_sp_rejected():
    from faabric_tpu.parallel.pipeline import make_pp_loss

    cfg = _moe_cfg()
    mesh = build_mesh(jax.devices()[:8], MeshConfig(sp=2, pp=2, ep=2))
    with pytest.raises(ValueError, match="compose with sp"):
        make_pp_loss(cfg, mesh)
