"""The flash kernels, the feed-forward kernel, the cached-attention
kernel, the selective-scan kernel, latent attention's prefill kernel and
the held experts' row accumulation compiled by the TPU's own
compiler for a described v5e, at real widths, without a chip: what the interpreter cannot refuse
(a block Mosaic cannot tile, more VMEM than a kernel may take) fails here
and costs no chip time. Nothing runs, so nothing is said about results
or speed. All of these stay in this one file: the worker that runs it is
the one that loads libtpu."""

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from faabric_tpu.ops import flash_attention
from faabric_tpu.ops.flash_attention import KERNELS, block_plan


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def as_on_tpu(monkeypatch):
    """The ops ask the backend which branch to take; the compile is for
    the described chip, so they are told "tpu" for the test's length. The
    persistent cache is off: an executable for a chip that is not there
    can be written to it and never read back."""
    from jax.experimental.compilation_cache import compilation_cache

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


# (q shape, k length, dtype, causal): train_2k_1chip's call and its
# four-chip twin's, the long sequences the whole-sequence blocks refused,
# a block of 384 (S = 128 · 9), float32 operands, a narrow head, a
# decode-like cross length, no mask
CALLS = {
    "train_2k_1chip": ((4, 2048, 16, 128), 2048, jnp.bfloat16, True),
    "train_2k_4chip_local": ((4, 2048, 8, 128), 2048, jnp.bfloat16, True),
    "seq_8192": ((1, 8192, 16, 128), 8192, jnp.bfloat16, True),
    "seq_16384": ((1, 16384, 16, 128), 16384, jnp.bfloat16, True),
    "seq_1152_blocks_of_384": ((2, 1152, 16, 128), 1152, jnp.bfloat16, True),
    "float32": ((2, 2048, 16, 128), 2048, jnp.float32, True),
    "head_dim_64": ((8, 1024, 16, 64), 1024, jnp.bfloat16, True),
    "cross_length": ((2, 1024, 16, 128), 4096, jnp.bfloat16, True),
    "non_causal": ((2, 2048, 16, 128), 2048, jnp.bfloat16, False),
}


@pytest.mark.parametrize("call", sorted(CALLS))
def test_flash_kernels_compile_for_v5e(call, one_chip, as_on_tpu):
    q_shape, s_k, dtype, causal = CALLS[call]
    k_shape = (q_shape[0], s_k, *q_shape[2:])
    plan = block_plan(q_shape, k_shape, causal, dtype=dtype)
    assert plan is not None and tuple(plan) == KERNELS

    def attention(q, k, v):
        return flash_attention(q, k, v, causal)

    def loss(q, k, v):
        return jnp.sum(attention(q, k, v).astype(jnp.float32))

    q = jax.ShapeDtypeStruct(q_shape, dtype, sharding=one_chip)
    kv = jax.ShapeDtypeStruct(k_shape, dtype, sharding=one_chip)
    forward = jax.jit(attention).lower(q, kv, kv).compile().as_text()
    both = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, kv, kv).compile().as_text()
    assert forward.count("tpu_custom_call") == 1
    assert both.count("tpu_custom_call") == 3


# (rows, d_model, d_ff): the cached steps of serve_granite_1chip and
# serve_longcat_1chip, and the rule's two edges at the first's widths
FFN_CALLS = {
    "serve_granite_1chip": (64, 2048, 8192),
    "serve_longcat_1chip": (64, 6144, 12288),
    "rows_8": (8, 2048, 8192),
    "rows_128": (128, 2048, 8192),
}


@pytest.mark.parametrize("call", sorted(FFN_CALLS))
def test_gated_ffn_compiles_for_v5e(call, one_chip, as_on_tpu):
    """The tile :func:`gated_ffn.plan` picks is one Mosaic can cut and the
    VMEM the call asks for is VMEM a kernel may have."""
    from faabric_tpu.ops.gated_ffn import gated_ffn, plan

    rows, d_model, d_ff = FFN_CALLS[call]
    how = plan(rows, d_model, d_ff, jnp.bfloat16)
    assert how is not None and how["tile"] % 128 == 0

    def shaped(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)

    compiled = jax.jit(gated_ffn).lower(
        shaped(rows, d_model), shaped(d_model, d_ff), shaped(d_model, d_ff),
        shaped(d_ff, d_model)).compile().as_text()
    assert compiled.count("tpu_custom_call") == 1
    assert "gated_ffn" in compiled


# (rows, positions, lanes, state size): a prefill chunk of
# serve_phi4flash_1chip, the smoke's longer chunk (three blocks of
# positions), a chunk the block does not divide, and one shorter than a
# sublane tile at the rule's fewest lanes
SCAN_CALLS = {
    "serve_phi4flash_1chip": (64, 256, 5120, 16),
    "smoke_384": (64, 384, 5120, 16),
    "short_last_block": (8, 200, 5120, 16),
    "five_positions": (3, 5, 128, 8),
}


@pytest.mark.parametrize("call", sorted(SCAN_CALLS))
def test_selective_scan_compiles_for_v5e(call, one_chip, as_on_tpu):
    """The tile and block :func:`selective_scan.plan` picks are ones
    Mosaic can cut, and the call fits the VMEM the plan names: it is
    compiled with no more than that and the headroom."""
    from faabric_tpu.ops.selective_scan import plan, selective_scan

    rows, length, lanes, n = SCAN_CALLS[call]
    how = plan(rows, length, lanes, n, jnp.bfloat16)
    assert how is not None and how["vmem_bytes"] < 48 * 1024 * 1024

    def shaped(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(selective_scan).lower(
        shaped(rows, length, lanes),
        shaped(rows, length, lanes, dtype=jnp.float32),
        shaped(rows, length, n), shaped(rows, length, n),
        shaped(n, lanes, dtype=jnp.float32),
        shaped(lanes, dtype=jnp.float32), shaped(rows, n, lanes)
    ).compile().as_text()
    assert compiled.count("tpu_custom_call") == 1
    assert "selective_scan" in compiled
    # S never lies in HBM but at the chunk's two ends: no loop is left
    assert "while(" not in compiled


# (rows, heads, key/value heads, slots, head_dim[, paired]): the cached
# step of serve_granite_1chip, the rule's lower edge at its widths, equal
# heads of 128 lanes over the longest reach a grid step holds, and
# serve_phi4flash_1chip's two calls in the differential form: the shared
# cache at the call's reach (the largest row a grid step takes) and a
# window's ring
ATTENTION_CALLS = {
    "serve_granite_1chip": (64, 32, 8, 640, 64),
    "rows_8": (8, 32, 8, 640, 64),
    "equal_heads_of_128": (16, 16, 16, 512, 128),
    "serve_phi4flash_1chip_shared": (64, 40, 20, 768, 64, True),
    "serve_phi4flash_1chip_ring": (64, 40, 20, 512, 64, True),
}


@pytest.mark.parametrize("call", sorted(ATTENTION_CALLS))
def test_cached_attention_compiles_for_v5e(call, one_chip, as_on_tpu):
    """The blocks :func:`cached_attention.plan` picks are blocks Mosaic
    can cut, the VMEM the call asks for is VMEM a kernel may have, and the
    caches are held to HBM (no copy of a whole cache into VMEM ahead)."""
    from faabric_tpu.ops.cached_attention import cached_attention, plan

    rows, heads, kv, slots, d, *paired = ATTENTION_CALLS[call]
    paired = bool(paired)
    assert plan(rows, heads, kv, slots, d, jnp.bfloat16,
                paired=paired) is not None

    def shaped(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(
        lambda q, k, v, n: cached_attention(q, k, v, n, 1 / 64,
                                            paired=paired)).lower(
        shaped(rows, heads, d), shaped(1, rows, slots, kv * d),
        shaped(1, rows, slots, kv * d), shaped(dtype=jnp.int32)
    ).compile().as_text()
    assert compiled.count("tpu_custom_call") == 1
    assert "cached_attention" in compiled
    assert "input_memory_space_colors" in compiled


# (rows, queries, reach): serve_axk1_1chip's first chunk, a middle one
# and its last at the rows their plan sends at once, 64 heads of 128 + 64
# lanes on values of 128; blocks the offset does not square
LATENT_CALLS = {
    "serve_axk1_1chip_first_chunk": (8, 1024, 1024),
    "serve_axk1_1chip_fourth_chunk": (4, 1024, 4096),
    "serve_axk1_1chip_last_chunk": (2, 1024, 8192),
    "an_offset_of_half_a_block": (2, 1024, 1536),
    "the_least_reach": (8, 256, 256),
}


@pytest.mark.parametrize("call", sorted(LATENT_CALLS))
def test_latent_attention_compiles_for_v5e(call, one_chip, as_on_tpu):
    """The blocks :func:`latent_attention.plan` picks are blocks Mosaic
    can cut (a head's lane tile of a row of all heads, rotary lanes of
    half a tile) within the VMEM a kernel may have; one kernel, and no
    array with a query and a key axis beside it."""
    from faabric_tpu.ops.latent_attention import latent_attention, plan

    rows, queries, reach = LATENT_CALLS[call]
    heads, nope, rope, v = 64, 128, 64, 128
    how = plan(rows, heads, queries, reach, nope, rope, v)
    assert how is not None and how["rows"] == rows

    def shaped(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)

    compiled = jax.jit(
        lambda *call: latent_attention(*call, scale=0.13086)).lower(
        shaped(rows, queries, heads, nope), shaped(rows, queries, heads, rope),
        shaped(rows, reach, heads * nope), shaped(rows, reach, rope),
        shaped(rows, reach, heads * v)).compile().as_text()
    assert compiled.count("tpu_custom_call") == 1
    assert "latent_attention" in compiled
    # (the output, (rows, queries, heads · v), is no such array)
    assert f"f32[{rows},{heads},{queries},{reach}]" not in compiled
    assert f"{heads},{queries},{reach}]" not in compiled


# (rows, heads, key/value heads, queries, slots, start, window, paired):
# serve_phi4flash_1chip's two chunks over a ring of 512 beside the chunk's
# own keys and over the shared cache of 768, serve_granite_1chip's two
# over its cache of 640; 64 lanes a head
PREFILL_CALLS = {
    "serve_phi4flash_1chip_ring_first": (64, 40, 20, 256, 512, 0, 512, True),
    "serve_phi4flash_1chip_ring_second": (64, 40, 20, 256, 512, 256, 512,
                                          True),
    "serve_phi4flash_1chip_full_second": (64, 40, 20, 256, 768, 256, 0,
                                          True),
    "serve_granite_1chip_first": (64, 32, 8, 256, 640, 0, 0, False),
    "serve_granite_1chip_second": (64, 32, 8, 256, 640, 256, 0, False),
}


@pytest.mark.parametrize("call", sorted(PREFILL_CALLS))
def test_prefill_attention_compiles_for_v5e(call, one_chip, as_on_tpu):
    """The blocks :func:`prefill_attention.plan` picks are blocks Mosaic
    can cut (a key/value tile of a position's heads, the query heads that
    read it side by side), the table of steps goes to SMEM, the caches
    are held to HBM, within the VMEM a kernel may have: one kernel, and
    no array with a query and a key axis beside it."""
    from faabric_tpu.ops.prefill_attention import plan, prefill_attention

    rows, heads, kv, queries, slots, start, window, paired = \
        PREFILL_CALLS[call]
    d = 64
    assert plan(rows, heads, kv, queries, slots, d, jnp.bfloat16, paired,
                start, window) is not None

    def shaped(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)

    own = (shaped(rows, queries, kv, d),) * 2 if window else ()
    compiled = jax.jit(
        lambda q, k, v, *own: prefill_attention(
            q, k, v, *own, start=start, scale=1 / 64, window=window,
            paired=paired)).lower(
        shaped(rows, queries, heads, d), shaped(1, rows, slots, kv * d),
        shaped(1, rows, slots, kv * d), *own).compile().as_text()
    assert compiled.count("tpu_custom_call") == 1
    assert "prefill_attention" in compiled
    assert "input_memory_space_colors" in compiled
    assert f",{queries},{slots}]" not in compiled
    assert f",{queries},{slots + queries}]" not in compiled


# (tokens, lanes of a row, tile): a prefill chunk of serve_axk1_1chip
# (8,192 tokens of 7,168 lanes) in its plan's tiles of 384 rows and one of
# serve_longcat_1chip (8,192 of 6,144) in tiles of 128; the same chunks
# in three and four pieces, as a bound on picks once cut them, in tiles
# of 128; a tile of 512 at A.X-K1's lanes, past Mosaic's default VMEM
# limit (the plan gives it to a call of 9,362 tokens, the most one piece
# holds there); a row narrower than a lane tile
ADD_ROWS_CALLS = {
    "serve_axk1_1chip_chunk": (8192, 7168, 384),
    "serve_longcat_1chip_chunk": (8192, 6144, 128),
    "serve_axk1_1chip_sub_chunk": (2731, 7168, 128),
    "serve_longcat_1chip_sub_chunk": (2048, 6144, 128),
    "a_tile_of_512_rows": (9362, 7168, 512),
    "a_row_of_64_lanes": (80, 64, 16),
}


@pytest.mark.parametrize("call", sorted(ADD_ROWS_CALLS))
def test_add_rows_compiles_for_v5e(call, one_chip, as_on_tpu):
    """A tile's rows go in and out of the accumulator one DMA a row along
    its untiled first axis (Mosaic copies no single row of a tiled one),
    the accumulator in place: one kernel, and no copy of the
    accumulator beside it."""
    from faabric_tpu.ops.add_rows import add_rows

    t, d, tile = ADD_ROWS_CALLS[call]
    layout = (-(-d // 128), 128)

    def shaped(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(add_rows, donate_argnums=0).lower(
        shaped((t, *layout), jnp.float32), shaped((tile,), jnp.int32),
        shaped((tile, *layout), jnp.bfloat16)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1
    assert "add_rows" in text
    assert not any(" copy(" in line and f"f32[{t}," in line
                   for line in text.splitlines())


def test_a_dense_cache_lies_at_its_values_bytes(one_chip, as_on_tpu):
    """Two cached steps of the cell's shape over caches laid as the
    program lays them, compiled: dense, both caches lie in HBM at their
    41.9 MB; head-major (as under a mesh, which never takes the kernel)
    and pinned row-major, a head of 64 lanes is padded to the tile's 128:
    XLA holds one of the two in VMEM whole (nothing holds it to HBM) and
    the one left in HBM takes 83.9 MB, both dense ones' bytes."""
    import json
    import os

    import numpy as np
    from jax.sharding import Mesh

    from benchmarks import program_granite
    from faabric_tpu.models import transformer
    from faabric_tpu.models.generate import _attention_cache_shapes

    rows, heads, kv, slots, d = ATTENTION_CALLS["serve_granite_1chip"]
    values = rows * slots * kv * d * 2
    with open(os.path.join(os.path.dirname(__file__), "..", "..",
                           "benchmarks", "configs",
                           "granite-4.0-h-micro.json")) as f:
        cfg = program_granite.model_config(json.load(f))

    def steps(mesh):
        shape = _attention_cache_shapes(cfg, rows, slots, mesh)["k"]

        def run(q, k, v):
            def step(carry, _):
                cache, pos = carry
                attn, cache = transformer._attend_cached(
                    q, k, v, cache, (0, pos), cfg, "attention", mesh)
                return (cache, pos + 1), attn
            cache = {name: jnp.zeros(shape, jnp.bfloat16)
                     for name in ("k", "v")}
            return jax.lax.scan(step, (cache, jnp.int32(600)), None,
                                length=2)[1]
        arg = jax.ShapeDtypeStruct((rows, 1, heads, d), jnp.bfloat16,
                                   sharding=one_chip)
        new = jax.ShapeDtypeStruct((rows, 1, kv, d), jnp.bfloat16,
                                   sharding=one_chip)
        compiled = jax.jit(run).lower(arg, new, new).compile()
        return compiled.memory_analysis().temp_size_in_bytes, \
            compiled.as_text()

    dense, text = steps(None)
    assert text.count("tpu_custom_call") == 1
    assert values == 41_943_040
    assert 2 * values <= dense < 2 * values + 1024 * 1024
    assert "bf16[1,64,640,512]{3,2,1,0:T(8,128)(2,1)S(1)}" not in text
    padded, text = steps(Mesh(np.array(list(one_chip.device_set)), ("dp",)))
    assert "tpu_custom_call" not in text
    assert "bf16[1,64,8,640,64]{4,3,2,1,0:T(8,128)(2,1)S(1)}" in text
    assert 2 * values <= padded < 2 * values + 1024 * 1024
