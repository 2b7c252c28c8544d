"""Attention (``models/layers._train_attention``): the share of the ``attn``
scope's device time, forward, backward and recompute, spent inside the
step's Mosaic kernels, %.

A kernel is an instruction of the step's compiled text that is a Mosaic
``custom-call`` (target ``tpu_custom_call``); XLA's own custom calls are
not kernels.  The reading counts the kernels that the text bills to
``attn`` (the flash-attention forward and backward kernels, where the
training path runs them), over all of that scope's time, the q/k/v/o
projections included.  It reads 0.0 where attention runs in XLA ops alone
(the blocked path), and None where ``read_scopes`` does.  Finding the
kernels takes the compiled text once more (``scopes.compiled_step_text``),
after the window.
"""
import re

from chipbench import harness, scopes

_KERNEL = re.compile(r"^\s+(?:ROOT )?%?([\w.\-]+) = .* custom-call\(.*"
                     r'custom_call_target="tpu_custom_call"')


def kernels(text: str) -> set:
    """The names of a compiled text's Mosaic kernel instructions."""
    return {m.group(1) for line in text.splitlines()
            if (m := _KERNEL.match(line))}


def read(rec, trace):
    got = scopes.read_scopes(rec, trace)
    if got is None or got.ms_per_step(("attn",)) is None:
        return None
    found = harness.load_cell(scopes.running_cell())
    try:
        text = scopes.compiled_step_text(found["config"], found["traffic"])
    except ValueError:
        return None
    names, where = kernels(text), scopes.instruction_scopes(text)
    kernel_s = sum(s for key, s in trace.ops.items()
                   if (name := scopes.instruction(key)) in names
                   and where[name][0] == "attn")
    return 100.0 * kernel_s / (got.total * got.share("attn"))
