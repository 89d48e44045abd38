"""The program's one vocabulary of scopes (``models/scopes.py``): what
``of_op_name`` makes of the names JAX writes, and that every model path
puts its work under exactly one phase and one sub-layer of it: the
optimized HLO of the toy configurations' ``generate`` and of the toy
train step, compiled on the CPU."""

import dataclasses
import re

import jax
import jax.numpy as jnp
import pytest

from faabric_tpu.models import (
    init_params,
    init_train_state,
    make_train_step,
    scopes,
)
from faabric_tpu.models.generate import _generate_impl
from tests.unit import test_granite, test_longcat, test_models, test_ouro

RETIRED = ("mla_prefill", "mla_decode", "gqa_attention", "ssm_prefill",
           "ssm_decode", "moe_route", "moe_experts", "dense_ffn", "ut_pass")

OP_NAMES = {
    "forward": ("jit(_generate_impl)/prefill/attention/dot_general",
                ("prefill", "attention")),
    "jvp": ("jit(step)/loss/jvp(attention)/bsd,dthe->tbshe/dot_general",
            ("loss", "attention")),
    "transpose": ("jit(step)/loss/transpose(jvp(feed_forward))/dot_general",
                  ("loss", "feed_forward")),
    "wrapped_phase": ("jit(f)/transpose(jvp(decode_step))/attention/mul",
                      ("decode_step", "attention")),
    "while_body": ("jit(_generate_impl)/while/body/closed_call/decode_step/"
                   "while/body/closed_call/feed_forward/dot_general",
                   ("decode_step", "feed_forward")),
    "remat": ("jit(step)/loss/transpose(jvp(loss))/jvp()/checkpoint/"
              "attention/bshe,hed->bsd/dot_general", ("loss", "attention")),
    "remat_of_scope": ("jit(step)/loss/remat(mixer)/rematted_computation/mul",
                       ("loss", "mixer")),
    "pallas_call": ("jit(_generate_impl)/prefill/attention/rms_norm/"
                    "pallas_call", ("prefill", "attention")),
    "optimizer": ("jit(step)/optimizer/jit(_where)/select_n",
                  ("optimizer", None)),
    "loop_counter": ("jit(_generate_impl)/prefill/while/body/add",
                     ("prefill", None)),
    "outermost_sublayer": ("jit(f)/prefill/feed_forward/attention/mul",
                           ("prefill", "feed_forward")),
    "jitted_function": ("jit(head)/decode_step/jit(sample)/dot_general",
                        ("decode_step", None)),
    "wrapped_function": ("jit(f)/jvp(jit(head))/mul", (None, None)),
    "joined": ("reshape;jit(step)/loss/jvp(attention)", ("loss", "attention")),
    "retired": ("jit(_generate_impl)/decode_step/ssm_decode/dot_general",
                ("decode_step", None)),
    "sublayer_alone": ("final_norm/reduce_sum", (None, "final_norm")),
    "parameter": ("params['blocks'][0]['wqkv']", (None, None)),
    "empty": ("", (None, None)),
    "none": (None, (None, None)),
}


@pytest.mark.parametrize("case", sorted(OP_NAMES))
def test_of_op_name(case):
    op_name, expected = OP_NAMES[case]
    assert scopes.of_op_name(op_name) == expected


def test_vocabulary_is_two_disjoint_levels():
    assert set(scopes.PHASES).isdisjoint(scopes.SUBLAYERS)
    assert len(set(scopes.PHASES + scopes.SUBLAYERS)) == 13
    assert not set(RETIRED) & set(scopes.PHASES + scopes.SUBLAYERS)
    # an instruction is placed by both levels; the optimizer has one
    assert scopes.placed("loss", "head") and scopes.placed("optimizer", None)
    assert not scopes.placed("loss", None)
    assert not scopes.placed(None, "head") and not scopes.placed(None, None)


# ---------------------------------------------------------------------------

_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%?[\w.\-]+ = .*? ([\w\-]+)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
WORK = ("dot", "convolution", "custom-call", "fusion")
# What JAX and XLA write around a loop or a checkpoint for themselves: the
# counter's add and compare, the copies of a body's operands
MACHINERY = {"while", "body", "cond", "closed_call", "checkpoint", "remat2",
             "rematted_computation", "jvp()", "transpose(jvp(loss))"}


def instructions_of(text: str) -> list:
    """(opcode, op_name) of the optimized HLO's instructions."""
    found = []
    for line in text.splitlines():
        m = _INSTRUCTION.match(line)
        if m:
            name = _OP_NAME.search(line)
            found.append((m.group(1), name.group(1) if name else ""))
    return found


def bookkeeping(op_name: str) -> bool:
    """An instruction a loop or a checkpoint made for itself: under the
    phase nothing but machinery and at most the primitive's name."""
    parts = [p for p in op_name.split("/")
             if not p.startswith("jit(") and p not in scopes.PHASES]
    inside = [p for p in parts if p not in MACHINERY]
    return len(inside) < len(parts) and len(inside) <= 1


def generate_text(cfg, params, rows, length, chunk=0) -> str:
    prompt = jnp.zeros((rows, length), jnp.int32)
    return _generate_impl.lower(
        params, prompt, cfg, 4, jax.random.PRNGKey(0), jnp.float32(1.0),
        True, 0, jnp.float32(1.0), False, None, chunk).compile().as_text()


def toy_pythia():
    cfg = test_models.CFG
    return cfg, init_params(jax.random.PRNGKey(0), cfg), 1, 8, 0


def toy_ouro():
    sz = test_ouro.sizes(3)
    return test_ouro.config(sz), test_ouro.weights(sz), 1, 16, 0


def toy_granite():
    sz = test_granite.sizes()
    return test_granite.config(sz), test_granite.weights(sz), 2, 16, 8


def toy_longcat():
    sz = test_longcat.sizes()
    return test_longcat.config(sz), test_longcat.weights(sz), 2, 16, 0


EVERY_MODEL = {"embed", "attention", "feed_forward", "final_norm", "head",
               "sample"}
SERVED = {
    "pythia": (toy_pythia, EVERY_MODEL),
    "ouro": (toy_ouro, EVERY_MODEL),
    "granite": (toy_granite, EVERY_MODEL | {"mixer"}),
    "longcat": (toy_longcat, EVERY_MODEL | {"router", "experts"}),
}


def check_scopes(text: str, phases: dict) -> None:
    """Every instruction that computes under a phase carries one sub-layer
    of it (the optimizer none), every sub-layer expected occurs, no other
    does, and no retired name is left."""
    seen = {phase: set() for phase in phases}
    for opcode, op_name in instructions_of(text):
        phase, sublayer = scopes.of_op_name(op_name)
        if phase is None or bookkeeping(op_name):
            continue
        assert phase in phases, (opcode, op_name)
        if opcode in WORK and phases[phase]:
            assert sublayer is not None, (opcode, op_name)
        seen[phase].add(sublayer)
    assert {phase: found - {None} for phase, found in seen.items()} == phases
    for name in RETIRED:
        assert not re.search(rf"[/(\"]{name}[/)\"]", text), name


@pytest.mark.parametrize("model", sorted(SERVED))
def test_generate_puts_all_work_under_its_scopes(model):
    make, sublayers = SERVED[model]
    cfg, params, rows, length, chunk = make()
    text = generate_text(cfg, params, rows, length, chunk)
    check_scopes(text, {"prefill": sublayers, "decode_step": sublayers})


@pytest.mark.parametrize("remat", (False, True), ids=("plain", "remat"))
def test_train_step_puts_all_work_under_its_scopes(remat):
    cfg = dataclasses.replace(test_models.CFG, remat=remat)
    params, opt_state = init_train_state(jax.random.PRNGKey(0), cfg)
    tokens = jnp.zeros((4, 16), jnp.int32)
    text = make_train_step(cfg).lower(
        params, opt_state, tokens, tokens).compile().as_text()
    check_scopes(text, {"loss": EVERY_MODEL - {"sample"}, "optimizer": set()})


def test_accumulated_step_divides_under_the_optimizer():
    """The gradient's division by ``accum_steps`` is the optimizer's: the
    jaxpr of the step names it so."""
    cfg = test_models.CFG
    params, opt_state = init_train_state(jax.random.PRNGKey(0), cfg)
    tokens = jnp.zeros((4, 16), jnp.int32)
    text = make_train_step(cfg, accum_steps=2).lower(
        params, opt_state, tokens, tokens).as_text(debug_info=True)
    assert re.search(r'optimizer/div', text)
    phases = {scopes.of_op_name(name)[0]
              for name in re.findall(r'loc\("([^"]*)"', text)}
    assert {"loss", "optimizer"} <= phases
