"""MoE model family: routing semantics + expert parallelism over ep."""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from faabric_tpu.models.moe import (
    MoEConfig,
    init_moe_params,
    make_moe_train_step,
    moe_forward,
    moe_loss_fn,
    moe_param_shardings,
)
from faabric_tpu.models.train import make_optimizer
from faabric_tpu.parallel import MeshConfig, build_mesh

CFG = MoEConfig(vocab_size=128, d_model=32, n_layers=2, n_heads=4, d_ff=64,
                max_seq=64, n_experts=4, compute_dtype=jnp.float32)


def batch(b=4, s=32, seed=0):
    rng = np.random.RandomState(seed)
    return (jnp.asarray(rng.randint(0, CFG.vocab_size, (b, s)), jnp.int32),
            jnp.asarray(rng.randint(0, CFG.vocab_size, (b, s)), jnp.int32))


def test_moe_forward_shapes_and_aux():
    params = init_moe_params(jax.random.PRNGKey(0), CFG)
    tokens, _ = batch()
    logits, aux = moe_forward(params, tokens, CFG)
    assert logits.shape == (4, 32, CFG.vocab_size)
    assert np.isfinite(np.asarray(logits)).all()
    # Switch aux loss is ~1 for a balanced router, bounded below by 1
    assert 0.9 < float(aux) < float(CFG.n_experts)


def test_moe_sharded_matches_single_device():
    """dp+ep+tp sharded MoE equals the unsharded computation."""
    params = init_moe_params(jax.random.PRNGKey(1), CFG)
    tokens, _ = batch()
    ref, aux_ref = moe_forward(params, tokens, CFG)

    mesh = build_mesh(config=MeshConfig(dp=2, tp=2, ep=2))
    sharded = jax.device_put(params, moe_param_shardings(mesh, CFG))
    out, aux = jax.jit(
        lambda p, t: moe_forward(p, t, CFG, mesh))(sharded, tokens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-4)
    np.testing.assert_allclose(float(aux), float(aux_ref), atol=1e-5)


def test_moe_train_step_reduces_loss_on_ep_mesh():
    mesh = build_mesh(config=MeshConfig(dp=2, tp=1, ep=4))
    opt = make_optimizer()
    params = jax.device_put(init_moe_params(jax.random.PRNGKey(0), CFG),
                            moe_param_shardings(mesh, CFG))
    opt_state = opt.init(params)
    step = make_moe_train_step(CFG, mesh, opt)
    tokens, targets = batch()
    losses = []
    for _ in range(4):
        params, opt_state, loss = step(params, opt_state, tokens, targets)
        losses.append(float(loss))
    assert losses[-1] < losses[0]
    assert all(np.isfinite(l) for l in losses)


def test_moe_capacity_drops_overflow_tokens():
    """With capacity factor << 1 most tokens drop to the residual path —
    forward stays finite and differentiable."""
    cfg = MoEConfig(vocab_size=128, d_model=32, n_layers=1, n_heads=4,
                    d_ff=64, max_seq=64, n_experts=4, capacity_factor=0.25,
                    compute_dtype=jnp.float32)
    params = init_moe_params(jax.random.PRNGKey(0), cfg)
    tokens, targets = batch()
    loss = moe_loss_fn(params, tokens, targets, cfg)
    assert np.isfinite(float(loss))
    grads = jax.grad(moe_loss_fn)(params, tokens, targets, cfg)
    assert all(np.isfinite(np.asarray(g)).all()
               for g in jax.tree.leaves(grads))


def test_moe_top2_routing_matches_manual():
    """router_top_k=2 routes each token through its two best experts with
    renormalized gates; ample capacity means nothing drops, so the layer
    equals a dense per-token mixture of the two selected experts."""
    from faabric_tpu.models.moe import _moe_layer

    cfg = MoEConfig(vocab_size=16, d_model=8, n_layers=1, n_heads=2,
                    d_ff=16, max_seq=8, n_experts=4, router_top_k=2,
                    capacity_factor=4.0, compute_dtype=jnp.float32)
    params = init_moe_params(jax.random.PRNGKey(3), cfg)
    blk = params["blocks"][0]
    rng = np.random.RandomState(3)
    x = jnp.asarray(rng.randn(1, 8, 8), jnp.float32)

    out, _ = _moe_layer(x, blk, cfg, None)

    # Manual dense mixture
    probs = np.asarray(jax.nn.softmax(
        x.astype(jnp.float32) @ blk["router"].astype(jnp.float32), axis=-1))
    w1 = np.asarray(blk["w1"], np.float32)
    w2 = np.asarray(blk["w2"], np.float32)
    xf = np.asarray(x, np.float32)
    expected = np.zeros_like(xf)
    for t in range(8):
        top2 = np.argsort(probs[0, t])[::-1][:2]
        g = probs[0, t, top2] / probs[0, t, top2].sum()
        for gi, ei in zip(g, top2):
            ff = np.asarray(jax.nn.gelu(xf[0, t] @ w1[ei])) @ w2[ei]
            expected[0, t] += gi * ff
    np.testing.assert_allclose(np.asarray(out), expected, atol=1e-4)


def test_moe_dropped_tokens_pass_residual_only():
    """Force every token to one expert with capacity for only TWO: the
    first two (slot-priority order) get expert output, the rest
    contribute exactly zero from the MoE path."""
    from faabric_tpu.models.moe import _capacity, _moe_layer

    cfg = MoEConfig(vocab_size=16, d_model=8, n_layers=1, n_heads=2,
                    d_ff=16, max_seq=8, n_experts=4, router_top_k=1,
                    capacity_factor=1.0, compute_dtype=jnp.float32)
    params = init_moe_params(jax.random.PRNGKey(4), cfg)
    blk = dict(params["blocks"][0])
    # Router forced: expert 0 wins for every token
    router = np.zeros((8, 4), np.float32)
    router[:, 0] = 100.0
    blk["router"] = jnp.asarray(router)

    rng = np.random.RandomState(4)
    # Positive activations so the biasless router's forced expert-0
    # column dominates for EVERY token (logit = 100·Σx > 0)
    x = jnp.asarray(np.abs(rng.randn(1, 8, 8)) + 0.1, jnp.float32)
    assert _capacity(cfg, 8) == 2  # 8 tokens · 1.0 / 4 experts

    out, _ = _moe_layer(x, blk, cfg, None)
    out = np.asarray(out)
    # Tokens 0-1 fit expert 0's buffer; tokens 2+ dropped → zero output
    assert np.abs(out[0, :2]).max() > 0
    np.testing.assert_allclose(out[0, 2:], 0.0, atol=1e-7)


def test_moe_top2_train_step_on_ep_mesh():
    from faabric_tpu.models import make_optimizer
    from faabric_tpu.parallel import MeshConfig, build_mesh

    cfg = MoEConfig(vocab_size=128, d_model=32, n_layers=2, n_heads=4,
                    d_ff=64, max_seq=32, n_experts=4, router_top_k=2,
                    compute_dtype=jnp.float32)
    mesh = build_mesh(jax.devices()[:8], MeshConfig(dp=2, ep=4))
    opt = make_optimizer()
    params = jax.device_put(init_moe_params(jax.random.PRNGKey(5), cfg),
                            moe_param_shardings(mesh, cfg))
    opt_state = opt.init(params)
    step = make_moe_train_step(cfg, mesh, opt)
    rng = np.random.RandomState(5)
    tokens = jnp.asarray(rng.randint(0, 128, (4, 32)), jnp.int32)
    losses = []
    for _ in range(3):
        params, opt_state, loss = step(params, opt_state, tokens, tokens)
        losses.append(float(loss))
    assert all(np.isfinite(v) for v in losses)
    assert losses[-1] < losses[0]


@pytest.mark.parametrize("kind", [dict(ffn="swiglu"),
                                  dict(norm_placement="sandwich"),
                                  dict(n_passes=2)],
                         ids=lambda kind: next(iter(kind)))
def test_moe_refuses_block_kinds_it_lacks(kind):
    """The expert layer is a GELU pair behind one pre-norm, passed once:
    another kind is refused by name at init and at forward, not computed
    as another network."""
    cfg = dataclasses.replace(CFG, **kind)
    (field,) = kind
    with pytest.raises(ValueError, match=field):
        init_moe_params(jax.random.PRNGKey(0), cfg)
    params = init_moe_params(jax.random.PRNGKey(0), CFG)
    with pytest.raises(ValueError, match=field):
        moe_forward(params, batch()[0], cfg)


def test_moe_honours_norm_eps_and_rope_pairing():
    """What the family can carry it carries: every norm of the layer
    takes the configuration's epsilon (the expert half's and the final
    one too), and the pairing reaches the attention half."""
    params = init_moe_params(jax.random.PRNGKey(0), CFG)
    # a residual stream small enough for epsilon to matter in every norm
    params["embed"] = params["embed"] * 1e-2
    tokens, _ = batch()
    base, _ = moe_forward(params, tokens, CFG)
    eps = 1e-2

    # no layers: the final norm alone, against the formula
    cfg = dataclasses.replace(CFG, norm_eps=eps, n_layers=0)
    got, _ = moe_forward({**params, "blocks": []}, tokens, cfg)
    x = params["embed"][tokens]
    want = (x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
            ) @ params["lm_head"]
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)

    loose, _ = moe_forward(params, tokens,
                           dataclasses.replace(CFG, norm_eps=eps))
    assert float(jnp.abs(loose - base).max()) > 1e-3
    halves, _ = moe_forward(params, tokens,
                            dataclasses.replace(CFG, rope_pairing="halves"))
    assert float(jnp.abs(halves - base).max()) > 1e-3
