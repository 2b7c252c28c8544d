"""SSD scan (``kernels/ssd.py``): the share of the ``ssd_scan`` scope's
device time, forward, backward and recompute, spent inside the step's Mosaic
kernels, %.

A kernel is an instruction of the step's compiled text that is a Mosaic
``custom-call`` (target ``tpu_custom_call``); XLA's own custom calls, such
as the ``AssumeGatherIndicesInBound`` of a gather, are not kernels.  The SSD
scan's forward and backward kernels are the chain runtime's only Mosaic
kernels, so every one this reading counts is theirs; it counts those that
the text bills to ``ssd_scan``, over all of that scope's time.  It reads 0.0
where the step has no Mosaic kernel (the scan in XLA ops alone), and None
where ``read_scopes`` does.  Finding the kernels takes the compiled text
once more (``scopes.compiled_step_text``), after the window.
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
    if got is None or got.ms_per_step(("ssd_scan",)) is None:
        return None
    found = harness.load_cell(scopes.running_cell())
    try:
        text = scopes.compiled_step_text(found["config"], found["traffic"])
    except ValueError:
        return None
    names, where = kernels(text), scopes.instruction_scopes(text)
    kernel_s = sum(s for key, s in trace.ops.items()
                   if (name := scopes.instruction(key)) in names
                   and where[name][0] == "ssd_scan")
    return 100.0 * kernel_s / (got.total * got.share("ssd_scan"))
