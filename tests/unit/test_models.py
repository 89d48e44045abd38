"""Flagship model + mesh tests on the 8-device virtual mesh."""

import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from faabric_tpu.models import (
    ModelConfig,
    data_sharding,
    forward,
    init_params,
    init_train_state,
    loss_fn,
    make_train_step,
    param_shardings,
)
from faabric_tpu.parallel import MeshConfig, build_mesh

CFG = ModelConfig(vocab_size=128, d_model=32, n_layers=2, n_heads=4,
                  d_ff=64, max_seq=32, compute_dtype=jnp.float32)


def tiny_batch(b=4, s=16, seed=0):
    rng = np.random.RandomState(seed)
    return (rng.randint(0, CFG.vocab_size, (b, s), dtype=np.int32),
            rng.randint(0, CFG.vocab_size, (b, s), dtype=np.int32))


def test_mesh_config_resolution():
    assert MeshConfig(tp=2, sp=2).resolve(8) == {
        "dp": 2, "tp": 2, "sp": 2, "pp": 1, "ep": 1}
    assert MeshConfig().resolve(8)["dp"] == 8
    with pytest.raises(ValueError):
        MeshConfig(tp=3).resolve(8)


def test_forward_shapes_and_determinism():
    params = init_params(jax.random.PRNGKey(0), CFG)
    tokens, _ = tiny_batch()
    logits = forward(params, jnp.asarray(tokens), CFG)
    assert logits.shape == (4, 16, CFG.vocab_size)
    logits2 = forward(params, jnp.asarray(tokens), CFG)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(logits2))


def test_causality():
    """Changing a future token must not change past logits."""
    params = init_params(jax.random.PRNGKey(0), CFG)
    tokens, _ = tiny_batch()
    logits_a = np.asarray(forward(params, jnp.asarray(tokens), CFG))
    tokens_mod = tokens.copy()
    tokens_mod[:, -1] = (tokens_mod[:, -1] + 1) % CFG.vocab_size
    logits_b = np.asarray(forward(params, jnp.asarray(tokens_mod), CFG))
    np.testing.assert_allclose(logits_a[:, :-1], logits_b[:, :-1], atol=1e-5)
    assert not np.allclose(logits_a[:, -1], logits_b[:, -1])


def test_sharded_forward_matches_single_device():
    """The dp/tp/sp-sharded computation must equal the unsharded one."""
    params = init_params(jax.random.PRNGKey(1), CFG)
    tokens, _ = tiny_batch()
    ref = np.asarray(forward(params, jnp.asarray(tokens), CFG))

    mesh = build_mesh(config=MeshConfig(dp=2, tp=2, sp=2))
    sharded_params = jax.device_put(params, param_shardings(mesh, CFG))
    sharded_tokens = jax.device_put(jnp.asarray(tokens), data_sharding(mesh))
    out = jax.jit(lambda p, t: forward(p, t, CFG, mesh))(
        sharded_params, sharded_tokens)
    np.testing.assert_allclose(np.asarray(out), ref, atol=2e-4)


def test_train_step_reduces_loss_on_mesh():
    mesh = build_mesh(config=MeshConfig(dp=2, tp=2, sp=2))
    params, opt_state = init_train_state(jax.random.PRNGKey(0), CFG, mesh)
    step = make_train_step(CFG, mesh)
    tokens, targets = tiny_batch()
    tokens = jax.device_put(jnp.asarray(tokens), data_sharding(mesh))
    targets = jax.device_put(jnp.asarray(targets), data_sharding(mesh))
    losses = []
    for _ in range(4):
        params, opt_state, loss = step(params, opt_state, tokens, targets)
        losses.append(float(loss))
    assert losses[-1] < losses[0]
    assert all(np.isfinite(l) for l in losses)


def test_multi_step_matches_sequential_steps():
    """n steps in one compiled scan == n sequential make_train_step
    calls (same optimizer, same batch every step)."""
    from faabric_tpu.models import make_multi_step, make_optimizer

    mesh = build_mesh(config=MeshConfig(dp=2, tp=2, sp=2))
    tokens, targets = tiny_batch()
    tokens = jax.device_put(jnp.asarray(tokens), data_sharding(mesh))
    targets = jax.device_put(jnp.asarray(targets), data_sharding(mesh))

    params, opt_state = init_train_state(jax.random.PRNGKey(0), CFG, mesh)
    step = make_train_step(CFG, mesh, make_optimizer())
    for _ in range(3):
        params, opt_state, loss_seq = step(params, opt_state, tokens, targets)

    params2, opt2 = init_train_state(jax.random.PRNGKey(0), CFG, mesh)
    run = make_multi_step(CFG, mesh, make_optimizer())
    params2, opt2, loss_scan = run(params2, opt2, tokens, targets, 3)
    np.testing.assert_allclose(float(loss_scan), float(loss_seq), rtol=2e-5)


def test_multi_step_per_step_batches():
    """A leading step axis feeds a fresh batch each step; mismatched
    length is rejected."""
    from faabric_tpu.models import make_multi_step

    mesh = build_mesh(config=MeshConfig(dp=4, tp=2))
    params, opt_state = init_train_state(jax.random.PRNGKey(0), CFG, mesh)
    run = make_multi_step(CFG, mesh)
    tokens, targets = tiny_batch()
    tok3 = jnp.stack([jnp.asarray(tokens)] * 3)
    tgt3 = jnp.stack([jnp.asarray(targets)] * 3)
    _, _, loss = run(params, opt_state, tok3, tgt3, 3)
    assert np.isfinite(float(loss))
    with pytest.raises(ValueError, match="per-step batches"):
        run(*init_train_state(jax.random.PRNGKey(0), CFG, mesh),
            tok3, tgt3, 4)


def test_param_shardings_cover_all_params():
    params = init_params(jax.random.PRNGKey(0), CFG)
    mesh = build_mesh(config=MeshConfig(tp=2))
    shardings = param_shardings(mesh, CFG)
    flat_p = jax.tree.leaves(params)
    flat_s = jax.tree.leaves(shardings,
                             is_leaf=lambda x: hasattr(x, "spec"))
    assert len(flat_p) == len(flat_s)


def test_graft_entry_contract():
    import __graft_entry__ as graft

    fn, args = graft.entry()
    out = jax.jit(fn)(*args)
    assert out.ndim == 3
    assert np.isfinite(np.asarray(out)).all()


def test_kv_cache_generation_matches_full_forward():
    """Greedy decode with the KV cache must equal re-running the full
    forward on the growing sequence (cache correctness)."""
    import jax.numpy as jnp

    from faabric_tpu.models.generate import generate

    cfg = CFG
    params = init_params(jax.random.PRNGKey(7), cfg)
    prompt = jnp.asarray(
        np.random.RandomState(7).randint(0, cfg.vocab_size, (2, 8)),
        dtype=jnp.int32)

    n_new = 6
    got = np.asarray(generate(params, prompt, cfg, n_new))

    # Reference: grow the sequence token by token through the full forward
    seq = np.asarray(prompt)
    expect = []
    for _ in range(n_new):
        logits = forward(params, jnp.asarray(seq), cfg)
        nxt = np.asarray(jnp.argmax(logits[:, -1], axis=-1), dtype=np.int32)
        expect.append(nxt)
        seq = np.concatenate([seq, nxt[:, None]], axis=1)
    expect = np.stack(expect, axis=1)
    np.testing.assert_array_equal(got, expect)


def _greedy_by_full_forward(params, prompt, cfg, served):
    """What the full forward picks after the prompt and after each served
    token but the last: causal, so one pass over the whole sequence is
    the growing sequence's every step."""
    seq = jnp.concatenate([prompt, jnp.asarray(served)[:, :-1]], axis=1)
    logits = forward(params, seq, cfg)[:, prompt.shape[1] - 1:]
    return np.asarray(jnp.argmax(logits, axis=-1), dtype=np.int32)


@pytest.mark.parametrize("max_seq,s_p,n_new,chunk,slots", [
    (512, 8, 6, 0, 128),      # the call's reach below one rounding unit
    (512, 120, 20, 0, 256),   # not a multiple of the unit: rounded up
    (256, 250, 6, 0, 256),    # the reach is exactly max_seq
    (200, 150, 50, 0, 200),   # rounding may not pass max_seq
    (512, 130, 10, 48, 256),  # chunked prefill with a ragged last chunk
], ids=["below_unit", "rounded_up", "exactly_max_seq", "capped_at_max_seq",
        "prefill_chunk"])
def test_generate_with_bounded_cache_matches_full_forward(
        max_seq, s_p, n_new, chunk, slots):
    """The cache holds what the call can fill (prompt + new tokens, in
    128s, at most max_seq); decoding over it equals the full forward on
    the growing sequence."""
    from faabric_tpu.models.generate import _cache_slots, generate

    cfg = ModelConfig(vocab_size=128, d_model=32, n_layers=2, n_heads=4,
                      d_ff=64, max_seq=max_seq, compute_dtype=jnp.float32)
    assert _cache_slots(cfg, s_p + n_new) == slots
    params = init_params(jax.random.PRNGKey(7), cfg)
    prompt = jnp.asarray(
        np.random.RandomState(s_p).randint(0, cfg.vocab_size, (2, s_p)),
        dtype=jnp.int32)
    got = np.asarray(generate(params, prompt, cfg, n_new,
                              prefill_chunk=chunk))
    np.testing.assert_array_equal(
        got, _greedy_by_full_forward(params, prompt, cfg, got))


def test_generate_beyond_max_seq_raises():
    """A call whose last write would lie past max_seq is refused (it used
    to overwrite the last slot in silence)."""
    from faabric_tpu.models.generate import generate

    params = init_params(jax.random.PRNGKey(0), CFG)
    prompt = jnp.zeros((1, CFG.max_seq - 4), jnp.int32)
    generate(params, prompt, CFG, 4)
    with pytest.raises(ValueError, match="max_seq"):
        generate(params, prompt, CFG, 5)


def test_init_kv_cache_is_head_major_one_a_pass_and_takes_its_slots():
    """One cache a pass a layer, (passes, batch, heads, slots, head_dim);
    the slot count is the caller's to give (a default of max_seq would be
    100 GB for a looped model of 65,536 positions)."""
    import dataclasses

    from faabric_tpu.models import init_kv_cache

    short = init_kv_cache(CFG, 3, 8)
    assert len(short) == CFG.n_layers
    assert short[1]["v"].shape == (1, 3, CFG.n_heads, 8, CFG.head_dim)
    assert short[1]["v"].dtype == CFG.compute_dtype
    looped = init_kv_cache(dataclasses.replace(CFG, n_passes=4), 3, 8)
    assert looped[0]["k"].shape == (4, 3, CFG.n_heads, 8, CFG.head_dim)
    with pytest.raises(TypeError):
        init_kv_cache(CFG, 3)


def _walk_jaxpr(jaxpr, inside_loop=False):
    """Every (equation, is it inside a loop's body) of a jaxpr, nested
    jaxprs included."""
    for eqn in jaxpr.eqns:
        yield eqn, inside_loop
        inner = inside_loop or eqn.primitive.name in ("scan", "while")
        for value in eqn.params.values():
            for sub in (value if isinstance(value, (list, tuple))
                        else [value]):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _walk_jaxpr(sub, inner)


def test_generate_is_one_loop_over_the_calls_own_slots():
    """The serve cell's shapes at toy widths, traced and not compiled:
    the program holds exactly one loop (``decode_hbm_share.serve`` divides
    the one ``while``'s span by its steps), and nothing inside it is as
    long as max_seq: a 128-token prompt with 64 new tokens attends 256
    slots."""
    from faabric_tpu.models.generate import generate

    cfg = ModelConfig(vocab_size=320, d_model=64, n_layers=2, n_heads=4,
                      d_ff=96, max_seq=2048, compute_dtype=jnp.float32)
    params = init_params(jax.random.PRNGKey(0), cfg)
    prompt = jnp.zeros((1, 128), jnp.int32)
    jaxpr = jax.make_jaxpr(lambda p, t: generate(p, t, cfg, 64))(
        params, prompt)

    walked = list(_walk_jaxpr(jaxpr.jaxpr))
    loops = [e.primitive.name for e, _ in walked
             if e.primitive.name in ("scan", "while")]
    assert loops == ["scan"]
    in_loop = [v.aval.shape for e, inside in walked if inside
               for v in list(e.invars) + list(e.outvars)
               if hasattr(v.aval, "shape")]
    assert in_loop, "the walk found nothing inside the scan"
    assert not [s for s in in_loop if cfg.max_seq in s]
    assert (1, cfg.n_heads, 256, cfg.head_dim) in in_loop


def test_generate_sampling_modes():
    """Greedy default unchanged; temperature/top-k/top-p sampling produce
    valid tokens, are deterministic per key, and vary across keys."""
    from faabric_tpu.models.generate import generate

    cfg = ModelConfig(vocab_size=64, d_model=32, n_layers=2, n_heads=4,
                      d_ff=64, max_seq=64, compute_dtype=jnp.float32)
    params = init_params(jax.random.PRNGKey(0), cfg)
    prompt = jnp.asarray(
        np.random.RandomState(0).randint(0, 64, (2, 8)), jnp.int32)

    greedy1 = generate(params, prompt, cfg, 8)
    greedy2 = generate(params, prompt, cfg, 8)
    np.testing.assert_array_equal(np.asarray(greedy1), np.asarray(greedy2))

    k1 = jax.random.PRNGKey(1)
    s1 = generate(params, prompt, cfg, 8, k1, 1.0, 16, 0.9)
    s1b = generate(params, prompt, cfg, 8, k1, 1.0, 16, 0.9)
    np.testing.assert_array_equal(np.asarray(s1), np.asarray(s1b))
    s2 = generate(params, prompt, cfg, 8, jax.random.PRNGKey(2), 1.0, 16,
                  0.9)
    assert not np.array_equal(np.asarray(s1), np.asarray(s2))
    for out in (greedy1, s1, s2):
        arr = np.asarray(out)
        assert arr.shape == (2, 8)
        assert (arr >= 0).all() and (arr < 64).all()


def test_top_p_cutoff_keeps_nucleus():
    """A spiked distribution with top_p=0.5 must only ever sample the
    dominant token."""
    from faabric_tpu.models.generate import _pick_token

    logits = jnp.asarray([[10.0, 0.0, 0.0, 0.0]])
    for seed in range(5):
        tok = _pick_token(logits, jax.random.PRNGKey(seed), False,
                          jnp.float32(1.0), 0, True, jnp.float32(0.5))
        assert int(tok[0]) == 0


def test_generate_under_tp_mesh_matches_single_device():
    """Tensor-parallel decode (params over tp, KV cache over dp x tp)
    produces the same greedy tokens as unsharded decode."""
    from faabric_tpu.models.generate import generate
    from faabric_tpu.models.transformer import param_shardings
    from faabric_tpu.parallel import MeshConfig, build_mesh

    cfg = ModelConfig(vocab_size=64, d_model=32, n_layers=2, n_heads=4,
                      d_ff=64, max_seq=64, compute_dtype=jnp.float32)
    params = init_params(jax.random.PRNGKey(0), cfg)
    prompt = jnp.asarray(
        np.random.RandomState(7).randint(0, 64, (2, 8)), jnp.int32)
    ref = np.asarray(generate(params, prompt, cfg, 8))

    mesh = build_mesh(jax.devices()[:8], MeshConfig(dp=2, tp=4))
    sharded = jax.device_put(params, param_shardings(mesh, cfg))
    sp = jax.device_put(prompt, jax.sharding.NamedSharding(
        mesh, jax.sharding.PartitionSpec("dp", None)))
    out = np.asarray(generate(sharded, sp, cfg, 8, mesh=mesh))
    np.testing.assert_array_equal(out, ref)


def test_chunked_prefill_matches_full_prefill():
    """Chunked prefill (incl. a ragged final chunk) produces identical
    greedy decode to whole-prompt prefill."""
    from faabric_tpu.models.generate import generate

    cfg = ModelConfig(vocab_size=64, d_model=32, n_layers=2, n_heads=4,
                      d_ff=64, max_seq=64, compute_dtype=jnp.float32)
    params = init_params(jax.random.PRNGKey(0), cfg)
    prompt = jnp.asarray(
        np.random.RandomState(11).randint(0, 64, (2, 21)), jnp.int32)
    full = np.asarray(generate(params, prompt, cfg, 8))
    chunked = np.asarray(generate(params, prompt, cfg, 8, prefill_chunk=8))
    np.testing.assert_array_equal(chunked, full)


def test_gradient_accumulation_matches_full_batch():
    """accum_steps=4 produces the same update as the full-batch step
    (equal microbatches, mean loss) — verified through one optimizer
    step on identical init."""
    from faabric_tpu.models import (
        init_train_state,
        make_optimizer,
        make_train_step,
    )

    mesh = build_mesh(config=MeshConfig(dp=2, tp=2, sp=2))
    tokens, targets = tiny_batch(b=8)
    t = jax.device_put(jnp.asarray(tokens), data_sharding(mesh))
    y = jax.device_put(jnp.asarray(targets), data_sharding(mesh))

    outs = {}
    for accum in (1, 4):
        opt = make_optimizer()
        params, opt_state = init_train_state(jax.random.PRNGKey(3), CFG,
                                             mesh, opt)
        step = make_train_step(CFG, mesh, opt, accum_steps=accum)
        params, _, loss = step(params, opt_state, t, y)
        outs[accum] = (float(loss), params)

    assert abs(outs[1][0] - outs[4][0]) < 1e-6
    for a, b in zip(jax.tree.leaves(outs[1][1]),
                    jax.tree.leaves(outs[4][1])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


def test_optimizer_schedule_and_clipping_train():
    from faabric_tpu.models import (
        init_train_state,
        make_optimizer,
        make_train_step,
    )

    mesh = build_mesh(config=MeshConfig(dp=8))
    opt = make_optimizer(lr=1e-3, warmup_steps=2, total_steps=20,
                         clip_norm=1.0)
    params, opt_state = init_train_state(jax.random.PRNGKey(0), CFG, mesh,
                                         opt)
    step = make_train_step(CFG, mesh, opt)
    tokens, targets = tiny_batch(b=8)
    t = jax.device_put(jnp.asarray(tokens), data_sharding(mesh))
    y = jax.device_put(jnp.asarray(targets), data_sharding(mesh))
    losses = []
    for _ in range(6):
        params, opt_state, loss = step(params, opt_state, t, y)
        losses.append(float(loss))
    assert all(np.isfinite(v) for v in losses)
    assert losses[-1] < losses[0]


# ---------------------------------------------------------------------------
# What the backward pass keeps of a block (remat_plan)
# ---------------------------------------------------------------------------

PYTHIA = dict(vocab_size=50304, d_model=2048, n_heads=16, d_ff=8192,
              max_seq=2048, attention_impl="flash")
MB = 1 << 20


def _keep_all(monkeypatch, free=1 << 50):
    """The plan as a chip with that many bytes free would make it: the
    reading is the one thing a test can steer (the CPU backend gives
    none)."""
    from faabric_tpu.models import transformer

    monkeypatch.setattr(transformer, "_free_bytes", lambda mesh: free)


def test_remat_plan_keeps_every_layer_at_the_train_cells_shapes():
    """``train_2k_1chip``: (4, 2048) tokens, 8 layers, one chip, 6.7 GB
    left beside the state and the step: all eight layers keep q, k and v
    (96 MiB), the kernel's output (32) and row statistic (4), the output
    projection (32), the up-projection (128: ISSUE 30's 306 MB so far)
    and the GELU's output (128), and nothing is computed twice."""
    from faabric_tpu.models.transformer import remat_plan

    cfg = ModelConfig(n_layers=8, **PYTHIA)
    plan = remat_plan(cfg, (4, 2048), None, 6_700_000_000)
    assert plan == {"layers_kept": 8, "layer_bytes": 420 * MB,
                    "kept_bytes": 8 * 420 * MB,
                    "recomputed_flops_per_token": 0}
    assert 305e6 < plan["layer_bytes"] - 128 * MB < 307e6


def test_remat_plan_keeps_what_fits_on_a_full_chip():
    """``train_2k_b8_dp2tp2``'s per-chip shapes (24 layers, 4 of 8
    sequences a chip, 8 of 16 heads and half of d_ff) with 1.7 GB left:
    the layers that fit, the first ones, and the others' forward counted
    as computed again."""
    from faabric_tpu.models.transformer import remat_plan

    cfg = ModelConfig(n_layers=24, **PYTHIA)
    mesh = build_mesh(jax.devices()[:4], MeshConfig(dp=2, tp=2))
    plan = remat_plan(cfg, (8, 2048), mesh, 1_700_000_000)
    # q, k, v 48, attention 16 + 2, the output projection whole 32, up
    # and the activation 64 MiB each
    assert plan["layer_bytes"] == 226 * MB
    assert plan["layers_kept"] == 7
    assert plan["kept_bytes"] == 7 * 226 * MB <= 1_700_000_000
    assert plan["kept_bytes"] + plan["layer_bytes"] > 1_700_000_000
    block = 2 * 2048 * (4 * 2048 + 2 * 8192) + 2 * 2048 * 2048
    assert plan["recomputed_flops_per_token"] == 17 * block
    assert remat_plan(cfg, (8, 2048), mesh, -5)["layers_kept"] == 0


@pytest.mark.parametrize("kinds,per_token", [
    (dict(), 3 * 64 + 64 + 2 * 96),
    (dict(attention_impl="flash"), 3 * 64 + 64 + 2 * 96 + 64),
    (dict(ffn="swiglu"), 3 * 64 + 64 + 3 * 96),
    (dict(norm_placement="sandwich"), 3 * 64 + 2 * 64 + 2 * 96),
])
def test_remat_plan_counts_what_each_kind_of_block_keeps(kinds, per_token):
    from faabric_tpu.models.transformer import remat_plan

    cfg = ModelConfig(vocab_size=320, d_model=64, n_layers=3, n_heads=4,
                      d_ff=96, max_seq=128, compute_dtype=jnp.float32,
                      **kinds)
    plan = remat_plan(cfg, (2, 128), None, 1 << 40)
    stat = 2 * 4 * 8 * 128 * 4 if kinds.get("attention_impl") else 0
    assert plan["layer_bytes"] == 2 * 128 * per_token * 4 + stat
    assert plan["layers_kept"] == 3


@pytest.mark.parametrize("kinds", [
    dict(attention_impl="flash"),
    dict(ffn="swiglu", norm_placement="sandwich"),
], ids=["gelu_pre_flash", "swiglu_sandwich"])
def test_a_kept_block_saves_the_bytes_the_plan_counts(capsys, kinds):
    """What ``jax.checkpoint`` saves of a block under the policy, beside
    the block's own arguments, is ``layer_bytes`` to the byte."""
    from jax.ad_checkpoint import print_saved_residuals

    from faabric_tpu.models import transformer

    cfg = ModelConfig(vocab_size=64, d_model=256, n_layers=1, n_heads=2,
                      d_ff=384, max_seq=128, compute_dtype=jnp.float32,
                      norm_impl="reference", **kinds)
    blk = init_params(jax.random.PRNGKey(0), cfg)["blocks"][0]
    x = jnp.zeros((4, 128, cfg.d_model))
    positions = jnp.zeros((4, 128), jnp.int32)
    keeping = jax.checkpoint(
        transformer._block, static_argnums=(3, 4),
        policy=jax.checkpoint_policies.save_only_these_names(
            *transformer.KEPT))
    print_saved_residuals(
        lambda x, blk: keeping(x, blk, positions, cfg, None)[0].sum(),
        x, blk)
    saved = 0
    for line in capsys.readouterr().out.splitlines():
        shape = re.match(r"(f32|i32|float32|int32)\[([\d,]*)\] (.*)", line)
        if shape and not shape.group(3).startswith(
                ("from the argument", "from a constant")):
            saved += 4 * int(np.prod(
                [int(n) for n in shape.group(2).split(",")]))
    plan = transformer.remat_plan(cfg, (4, 128), None, 1 << 40)
    assert saved == plan["layer_bytes"] > 0


def test_remat_plan_without_a_memory_reading_keeps_nothing():
    """The CPU backend reads no memory: every block is checkpointed
    whole, and the plan says what that computes twice."""
    from faabric_tpu.models import transformer

    cfg = ModelConfig(n_layers=8, **PYTHIA)
    assert transformer._free_bytes(None) is None
    plan = transformer.remat_plan(cfg, (4, 2048), None, None)
    assert plan["layers_kept"] == 0 and plan["kept_bytes"] == 0
    # 402.7 M block parameters at 2 a token, and causal attention
    assert plan["recomputed_flops_per_token"] == 2 * 402_653_184 \
        + 8 * 2 * 2048 * 2048


@pytest.mark.parametrize("layers,batch,ways,in_use,temp,kept", [
    # state and the compiled step's temporaries as the chip reported them
    # (PERF.md section 5: 7.43 + 2.71 GB; section 7: 8.56 + 6.67 GB)
    (8, 4, None, 7_429_755_904, 2_709_635_072, 8),
    (24, 8, dict(dp=2, tp=2), 8_560_000_000, 6_670_000_000, 3),
])
def test_step_bytes_is_on_the_safe_side_of_what_the_chip_reported(
        layers, batch, ways, in_use, temp, kept):
    """The step's own needs, from shapes, are no less than the whole-block
    step's measured temporaries, so that what the plan adds still fits;
    and the chip's reading at trace time (its limit less the state) gives
    the train cell all eight layers and the full four-chip job a few."""
    from faabric_tpu.models.transformer import remat_plan, step_bytes

    limit = 16_909_336_064
    cfg = ModelConfig(n_layers=layers, **PYTHIA)
    mesh = ways and build_mesh(jax.devices()[:4], MeshConfig(**ways))
    needs = step_bytes(cfg, (batch, 2048), mesh)
    assert needs >= temp
    plan = remat_plan(cfg, (batch, 2048), mesh, limit - in_use - needs)
    assert plan["layers_kept"] == kept
    assert in_use + temp + plan["kept_bytes"] < limit


FLASH_TOY = dict(vocab_size=64, d_model=256, n_layers=2, n_heads=2, d_ff=384,
                 max_seq=128, compute_dtype=jnp.float32,
                 attention_impl="flash")


def _forward_uses(jaxpr, cfg):
    """How often each block matrix enters a product that contracts its
    input side (a forward product: the backward's contract the output
    side, or make the matrix's own shape), and how many ``flash_fwd``
    kernels the jaxpr holds."""
    d, h, e, f = cfg.d_model, cfg.n_heads, cfg.head_dim, cfg.d_ff
    matrices = {(d, 3, h, e): {0}, (h, e, d): {0, 1}, (d, f): {0},
                (f, d): {0}}
    uses = dict.fromkeys(matrices, 0)
    kernels = 0
    for eqn, _ in _walk_jaxpr(jaxpr):
        if eqn.primitive.name == "pallas_call":
            kernels += eqn.params["name"] == "flash_fwd"
        if eqn.primitive.name != "dot_general":
            continue
        contract = eqn.params["dimension_numbers"][0]
        for operand, dims in zip(eqn.invars, contract):
            shape = tuple(operand.aval.shape)
            if matrices.get(shape) == set(dims):
                uses[shape] += 1
    return uses, kernels


@pytest.mark.parametrize("ways", [None, dict(dp=2, tp=2)],
                         ids=["one_chip", "dp2tp2"])
def test_kept_blocks_run_their_products_and_flash_fwd_once(monkeypatch,
                                                            ways):
    """The guard that the mechanism engages, on the jaxpr of the loss's
    gradient at a flash-eligible shape: under the keeping plan every
    block matrix enters one forward product and the jaxpr holds one
    ``flash_fwd`` a layer (under a mesh too, where the kernel sits inside
    a ``shard_map``); checkpointed whole, the QKV, output and up
    projections and the kernel run twice."""
    cfg = ModelConfig(**FLASH_TOY)
    mesh = ways and build_mesh(jax.devices()[:4], MeshConfig(**ways))
    params = init_params(jax.random.PRNGKey(0), cfg)
    tokens = jnp.zeros((4, 128), jnp.int32)  # no activation shaped as wo

    def grad_jaxpr():
        return jax.make_jaxpr(jax.grad(
            lambda p: loss_fn(p, tokens, tokens, cfg, mesh)))(params).jaxpr

    d, h, e, f = cfg.d_model, cfg.n_heads, cfg.head_dim, cfg.d_ff
    uses, kernels = _forward_uses(grad_jaxpr(), cfg)
    assert kernels == 2 * cfg.n_layers
    assert uses == {(d, 3, h, e): 4, (h, e, d): 4, (d, f): 4, (f, d): 2}

    _keep_all(monkeypatch)
    uses, kernels = _forward_uses(grad_jaxpr(), cfg)
    assert kernels == cfg.n_layers
    assert set(uses.values()) == {cfg.n_layers}

    # one layer's worth of room: the first block keeps, the second does not
    from faabric_tpu.models import transformer

    one = transformer.remat_plan(cfg, tokens.shape, mesh, 1 << 50)
    _keep_all(monkeypatch, transformer.step_bytes(cfg, tokens.shape, mesh)
              + one["layer_bytes"])
    uses, kernels = _forward_uses(grad_jaxpr(), cfg)
    assert kernels == cfg.n_layers + 1
    assert uses[(d, f)] == cfg.n_layers + 1


@pytest.mark.parametrize("kinds", [
    dict(ffn="gelu", norm_placement="pre"),
    dict(ffn="swiglu", norm_placement="sandwich"),
    dict(ffn="gelu", norm_placement="pre", attention_impl="flash",
         d_model=256, n_heads=2),
], ids=["gelu_pre", "swiglu_sandwich", "gelu_pre_flash"])
def test_keeping_plan_changes_no_gradient(monkeypatch, kinds):
    """The same products in the same precisions, kept instead of made
    twice: loss and gradients under the keeping plan are those of
    ``remat=False`` and of whole-block recomputation, to float32
    round-off."""
    import dataclasses

    cfg = ModelConfig(**{**dict(vocab_size=96, d_model=64, n_layers=2,
                                n_heads=4, d_ff=160, max_seq=128,
                                compute_dtype=jnp.float32), **kinds})
    params = init_params(jax.random.PRNGKey(3), cfg)
    rng = np.random.RandomState(3)
    tokens, targets = (jnp.asarray(rng.randint(0, 96, (2, 128)), jnp.int32)
                       for _ in range(2))

    def value_and_grads(c):
        return jax.jit(jax.value_and_grad(
            lambda p: loss_fn(p, tokens, targets, c)))(params)

    plain = value_and_grads(dataclasses.replace(cfg, remat=False))
    whole = value_and_grads(cfg)
    _keep_all(monkeypatch)  # each call above traces anew, plan and all
    kept = value_and_grads(cfg)
    for other in (plain, whole):
        np.testing.assert_allclose(kept[0], other[0], rtol=1e-6)
        for a, b in zip(jax.tree.leaves(kept[1]), jax.tree.leaves(other[1])):
            np.testing.assert_allclose(a, b, rtol=2e-4, atol=1e-6)


def test_kept_names_leave_generate_as_it_was(monkeypatch):
    """The serve cells run ``_block`` outside any checkpoint: the names
    are in ``generate()``'s jaxpr and lower to nothing, so the program is
    the one that a block without names gives."""
    from faabric_tpu.models import transformer
    from faabric_tpu.models.generate import generate

    cfg = ModelConfig(vocab_size=320, d_model=64, n_layers=2, n_heads=4,
                      d_ff=96, max_seq=256, compute_dtype=jnp.float32)
    params = init_params(jax.random.PRNGKey(0), cfg)
    prompt = jnp.zeros((1, 32), jnp.int32)

    def lowered():
        jax.clear_caches()
        fn = jax.jit(lambda p, t: generate(p, t, cfg, 8))
        names = [e for e, _ in _walk_jaxpr(
            jax.make_jaxpr(fn)(params, prompt).jaxpr)
            if e.primitive.name == "name"]
        return names, fn.lower(params, prompt).as_text()

    names, with_names = lowered()
    assert {e.params["name"] for e in names} >= {"q_rope", "attn_proj",
                                                 "ffn_act"}
    monkeypatch.setattr(transformer, "checkpoint_name", lambda x, name: x)
    names, without = lowered()
    assert not names
    # but for the counter in the names of jnp's private functions
    numbered = re.compile(r"(@_?[a-z_]+?)_\d+\b")
    assert numbered.sub(r"\1", with_names) == numbered.sub(r"\1", without)


# ---------------------------------------------------------------------------
# Which cached call attends a dense cache through ops/cached_attention.py
# ---------------------------------------------------------------------------

def _grouped(**other):
    """4 query heads on 2 key/value heads of 64 lanes: a position's keys
    are one row of 128 lanes."""
    return ModelConfig(**{**dict(
        vocab_size=96, d_model=256, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=128, max_seq=256, ffn="swiglu", compute_dtype=jnp.float32,
        param_dtype=jnp.float32, remat=False), **other})


# name → (what the configuration names, rows, positions a row, the cache's
# type or None for the compute type, under a mesh, the plan found)
ATTENDED = {
    "rows_8": ({}, 8, 1, None, False, True),
    "rows_64": ({}, 64, 1, None, False, True),
    "equal_heads_of_128": ({"n_heads": 2, "n_kv_heads": 0}, 8, 1, None,
                           False, True),
    "bfloat16": ({"compute_dtype": jnp.bfloat16}, 8, 1, None, False, True),
    "rotary_positions": ({"position": "rope"}, 8, 1, None, False, True),
    "one_row": ({}, 1, 1, None, False, False),
    "rows_7": ({}, 7, 1, None, False, False),
    "a_prefill_chunk": ({}, 8, 16, None, False, False),
    "latent_attention": (
        {"attention": "latent", "n_kv_heads": 0, "q_lora_rank": 32,
         "kv_lora_rank": 64, "qk_nope_dim": 32, "qk_rope_dim": 64,
         "v_head_dim": 64}, 8, 1, None, False, False),
    "a_mesh": ({}, 8, 1, None, True, False),
    "a_float32_cache_under_bfloat16_products": (
        {"compute_dtype": jnp.bfloat16}, 8, 1, jnp.float32, False, False),
    "keys_of_96_lanes_a_position": ({"d_model": 192}, 8, 1, None, False,
                                    False),
}


@pytest.mark.parametrize("case", sorted(ATTENDED))
def test_which_cached_call_attends_a_dense_cache(case):
    """The rule is over what the call can see: one position a row, 8 rows
    or more, attention over heads, no mesh, the cache in the compute type,
    a position's keys whole lanes. The cache's layout, the kernel in the
    traced step and ``call_sizes`` follow it alike."""
    from faabric_tpu.models import init_kv_cache, transformer
    from faabric_tpu.models.generate import call_sizes, forward_with_cache

    named, rows, positions, cache_dtype, meshed, found = ATTENDED[case]
    cfg = _grouped(**named)
    mesh = build_mesh(jax.devices()[:1], MeshConfig()) if meshed else None
    slots = 128
    how = transformer.streams_attention(
        cfg, rows, positions, slots, cache_dtype or cfg.compute_dtype, mesh)
    assert (how is not None) == found
    if cfg.attention == "latent" or cache_dtype is not None:
        return
    # the layout is the call's (one position a step), whatever this
    # forward's positions are
    dense = transformer.streams_attention(
        cfg, rows, 1, slots, cfg.compute_dtype, mesh) is not None
    cache = jax.eval_shape(lambda: init_kv_cache(cfg, rows, slots, mesh))
    width = cfg.kv_heads * cfg.head_dim
    assert cache[0]["k"].shape == (
        (1, rows, slots, width) if dense
        else (1, rows, cfg.kv_heads, slots, cfg.head_dim))
    params = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    tokens = jax.ShapeDtypeStruct((rows, positions), jnp.int32)
    jaxpr = jax.make_jaxpr(lambda p, t, c: forward_with_cache(
        p, t, c, 20, cfg, mesh=mesh))(params, tokens, cache)
    kernels = [e.params["name"] for e, _ in
               _walk_jaxpr(jaxpr.jaxpr) if e.primitive.name == "pallas_call"]
    assert kernels.count("cached_attention") == found * cfg.n_layers
    if positions == 1 and not meshed:
        sized = call_sizes(cfg, rows, 100, 20)
        assert sized["attention_streamed_layers"] == found * cfg.n_layers
        assert sized["attention_streamed_bytes"] == (
            found * cfg.n_layers * 2 * rows * slots * width
            * jnp.dtype(cfg.compute_dtype).itemsize)


@pytest.mark.parametrize("named", [
    {}, {"position": "rope", "n_passes": 2, "norm_placement": "sandwich"}],
    ids=["grouped", "looped_rotary"])
def test_eight_rows_together_serve_what_each_row_serves_alone(named):
    """Rows do not mix: 8 rows served together (a dense cache, prefill in
    two chunks over a view of it, every step through the kernel) give the
    tokens of the same rows served one at a time (a head-major cache under
    the block's own lines)."""
    from faabric_tpu.models.generate import call_sizes, generate

    cfg = _grouped(**named)
    params = init_params(jax.random.PRNGKey(3), cfg)
    prompt = jnp.asarray(np.random.RandomState(5).randint(
        0, cfg.vocab_size, (8, 21)), jnp.int32)
    assert call_sizes(cfg, 8, 21, 9)["attention_streamed_layers"] == 2
    assert call_sizes(cfg, 1, 21, 9)["attention_streamed_layers"] == 0
    together = np.asarray(generate(params, prompt, cfg, 9, prefill_chunk=16))
    alone = np.concatenate([
        np.asarray(generate(params, prompt[i:i + 1], cfg, 9,
                            prefill_chunk=16)) for i in range(8)])
    np.testing.assert_array_equal(together, alone)
