"""The one vocabulary of ``jax.named_scope``s in the model code: where an
instruction of a compiled program comes from, in two levels and no third.

A *phase* is what the jitted program is doing: ``prefill`` and
``decode_step`` (``generate._generate_impl``), ``loss`` (forward and
backward) and ``optimizer`` (``train._build_step``). A *sub-layer*, inside
a phase, is the part of the model at work, whatever the configuration's
kind of it: ``attention`` is per-head, grouped or latent, ``mixer`` the
state-space one. The optimizer has no sub-layers: it is one pass over
the leaves.

A scope is metadata. It reaches the ``op_name`` of every HLO instruction
traced under it and changes no operation of the compiled program, so it
costs nothing with tracing off; a reader that joins a device trace's events
to the program's instructions (``benchmarks/scope_times.py``) gives device
time by scope. A new mechanism takes the sub-layer scope of the function
it lives in, never a name of its own.

JAX's persistent compilation cache leaves metadata out of its key
(``jax_compilation_cache_include_metadata_in_key``): a program compiled
before a scope moved and loaded from a warm cache carries the old names.
"""

from __future__ import annotations

import re

PREFILL, DECODE_STEP, LOSS, OPTIMIZER = PHASES = (
    "prefill", "decode_step", "loss", "optimizer")
(EMBED, ATTENTION, MIXER, FEED_FORWARD, ROUTER, EXPERTS, FINAL_NORM, HEAD,
 SAMPLE) = SUBLAYERS = (
    "embed", "attention", "mixer", "feed_forward", "router", "experts",
    "final_norm", "head", "sample")

# ``jvp(decode_step)``, ``transpose(jvp(loss))``, ``remat(attention)``: a
# transformation goes round the scope it was applied under
_WRAPPED = re.compile(r"\w+\((.*)\)")
# ``jit(head)`` names a function that was jitted, not a scope
_FUNCTION = re.compile(r"p?jit\(.*\)")


def of_op_name(op_name: str) -> tuple:
    """(phase or None, sub-layer or None) of an instruction's ``op_name``,
    ``jit(_generate_impl)/decode_step/while/body/attention/dot_general``:
    the outermost of each level among the path's parts. What JAX writes
    beside the scopes is skipped (``while``, ``body``, ``cond``,
    ``checkpoint``, ``rematted_computation``, ``pallas_call``, the
    primitive's name: none is in the vocabulary) or unwrapped (``jvp``,
    ``transpose``, ``remat``, ``vmap`` and whatever else goes round a
    name in brackets)."""
    phase = sublayer = None
    for part in (op_name or "").split("/"):
        while not _FUNCTION.fullmatch(part) and (
                inner := _WRAPPED.fullmatch(part)):
            part = inner.group(1)
        if phase is None and part in PHASES:
            phase = part
        elif sublayer is None and part in SUBLAYERS:
            sublayer = part
    return phase, sublayer


def placed(phase, sublayer) -> bool:
    """Whether a (phase, sub-layer) says where an instruction belongs:
    both levels, or the optimizer, which has no sub-layers."""
    return phase is not None and (sublayer is not None or phase == OPTIMIZER)
