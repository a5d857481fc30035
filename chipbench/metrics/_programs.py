"""Shared by the trace readers: which trace events belong to which part
of the round program. Not a metric (no metric is named with a leading
underscore)."""
import re

#: the round program (``core.round.make_train_loop``) in the trace
ROUND_MODULE = r"train_loop"
KERNEL = re.compile(r'^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=.*custom_call_target='
                    r'"tpu_custom_call"', re.M)


def kernel_ops(r) -> set:
    """Names of the Mosaic (Pallas) kernel instructions in the compiled
    round program, as they name the trace's operation events."""
    text = (getattr(r, "programs", None) or {}).get("round", "")
    return set(KERNEL.findall(text))


def round_ops(r, dev):
    return r.trace.in_modules(dev, ROUND_MODULE)
